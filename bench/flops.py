"""Operations and bytes an sm-cnn scorer call needs, from its shapes.

The same for every backend that implements the scorer: a kernel that does
extra work (padding rows, recomputation) does not count it here.

Per pair, each arm is a wide convolution: (max_len + width - 1) windows,
each a (width * embed_dim) x conv_filters product, 2 operations per
multiply-add. The join layer is (2 * filters + extra) x hidden, the output
layer hidden x 2. Bytes are the least a call must move: the embedding rows
its tokens gather (float32), the token ids, features and scores, and every
weight but the embedding table once per call.
"""
from __future__ import annotations

from typing import Dict, Tuple

F32 = 4
I32 = 4


def pair_flops(m: Dict) -> int:
    w, d, f = m["filter_width"], m["embed_dim"], m["conv_filters"]
    windows = m["max_len"] + w - 1
    conv = 2 * windows * (w * d) * f * 2
    join = (2 * f + m["n_extra_feats"]) * m["n_hidden"] * 2
    out = m["n_hidden"] * 2 * 2
    return conv + join + out


def call_flops(m: Dict, rows: int) -> int:
    return rows * pair_flops(m)


def call_bytes(m: Dict, rows: int) -> int:
    w, d, f, h = (m["filter_width"], m["embed_dim"], m["conv_filters"],
                  m["n_hidden"])
    j_in = 2 * f + m["n_extra_feats"]
    weights = F32 * (2 * (w * d * f + f) + j_in * h + h + h * 2 + 2)
    per_pair = (2 * m["max_len"] * (I32 + d * F32)   # ids + gathered rows
                + m["n_extra_feats"] * F32 + F32)    # features + score
    return weights + rows * per_pair


def least_time_s(m: Dict, rows: int, peak_flops: float,
                 peak_bytes_s: float) -> Tuple[float, str]:
    """(seconds, bound): the larger of operations over peak and bytes over
    bandwidth, and which of the two it is."""
    t_flops = call_flops(m, rows) / peak_flops
    t_bytes = call_bytes(m, rows) / peak_bytes_s
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
