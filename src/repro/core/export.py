"""Language-agnostic weight serialization — the paper's Avro analogue.

The paper exports trained PyTorch weights via an Avro schema: every tensor is
flattened to one dimension with its dims saved as metadata, then restored on
the Java side. This module implements the same record layout natively:

  MAGIC | u64 header_len | JSON header | concatenated raw buffers

Header: {"schema_version", "model", "meta", "tensors": [{name, dtype, shape,
offset, nbytes}]}. Buffers are little-endian C-order — readable from any
language with a JSON parser (the interoperability property Avro provided).
``numpy_eval`` consumes these files with zero JAX imports.
"""
from __future__ import annotations

import io
import json
from typing import Any, Dict, Optional, Tuple

import numpy as np

try:  # export works from JAX pytrees, but the reader side never needs jax
    import jax
except ImportError:  # pragma: no cover
    jax = None

MAGIC = b"RPROAVRO1\n"
SCHEMA_VERSION = 1


def _flatten_named(params) -> Dict[str, np.ndarray]:
    flat = {}
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        flat[name] = np.asarray(leaf)
    return flat


def dumps(params: Any, model: str = "", meta: Optional[Dict] = None) -> bytes:
    """Serialize a params pytree (or a {name: array} dict) to bytes."""
    if isinstance(params, dict) and all(isinstance(v, np.ndarray)
                                        for v in params.values()):
        flat = dict(params)
    else:
        flat = _flatten_named(params)
    tensors, buf = [], io.BytesIO()
    offset = 0
    for name in sorted(flat):
        arr = np.asarray(flat[name])
        shape = list(arr.shape)  # before ascontiguousarray (it 1-d-ifies 0-d)
        arr = np.ascontiguousarray(arr)
        if str(arr.dtype) == "bfloat16":  # not portable across runtimes
            arr = arr.astype(np.float32)
        raw = arr.tobytes()
        tensors.append({"name": name, "dtype": str(arr.dtype),
                        "shape": shape, "offset": offset,
                        "nbytes": len(raw)})
        buf.write(raw)
        offset += len(raw)
    header = json.dumps({"schema_version": SCHEMA_VERSION, "model": model,
                         "meta": meta or {}, "tensors": tensors}).encode()
    out = io.BytesIO()
    out.write(MAGIC)
    out.write(len(header).to_bytes(8, "little"))
    out.write(header)
    out.write(buf.getvalue())
    return out.getvalue()


def loads(data: bytes) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Parse bytes -> ({name: np.ndarray}, header). Pure numpy."""
    if not data.startswith(MAGIC):
        raise ValueError("bad magic: not a repro export file")
    hlen = int.from_bytes(data[len(MAGIC):len(MAGIC) + 8], "little")
    hstart = len(MAGIC) + 8
    header = json.loads(data[hstart:hstart + hlen])
    if header["schema_version"] != SCHEMA_VERSION:
        raise ValueError(f"schema_version {header['schema_version']} != {SCHEMA_VERSION}")
    body = hstart + hlen
    out = {}
    for t in header["tensors"]:
        raw = data[body + t["offset"]: body + t["offset"] + t["nbytes"]]
        out[t["name"]] = np.frombuffer(raw, dtype=np.dtype(t["dtype"])
                                       ).reshape(t["shape"]).copy()
    return out, header


def save(path: str, params, model: str = "", meta: Optional[Dict] = None):
    with open(path, "wb") as f:
        f.write(dumps(params, model, meta))


def load(path: str) -> Tuple[Dict[str, np.ndarray], Dict]:
    with open(path, "rb") as f:
        return loads(f.read())


def restore_into(template, flat: Dict[str, np.ndarray]):
    """Rebuild a pytree with the template's structure from named tensors
    (the Java-side 'reshape using saved dimension metadata' step)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in paths:
        name = jax.tree_util.keystr(path, simple=True, separator="/")
        if name not in flat:
            raise KeyError(f"tensor {name!r} missing from export")
        arr = flat[name]
        if list(arr.shape) != list(leaf.shape):
            raise ValueError(f"{name}: shape {arr.shape} != {leaf.shape}")
        leaves.append(arr.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)
