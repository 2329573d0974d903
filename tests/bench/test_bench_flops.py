"""Operations and bytes of an sm-cnn scorer call, against a hand count."""
import pytest

from bench import flops
from bench.peaks import peaks

PUBLISHED = {"vocab_size": 30000, "embed_dim": 50, "conv_filters": 100,
             "filter_width": 5, "n_extra_feats": 4, "n_hidden": 204,
             "max_len": 64}


def test_pair_flops_hand_count():
    # 2 arms x 68 windows x (5*50) x 100 x 2, + 204 x 204 x 2, + 204 x 2 x 2
    assert flops.pair_flops(PUBLISHED) == 6_800_000 + 83_232 + 816
    assert flops.pair_flops(PUBLISHED) == pytest.approx(6.9e6, rel=0.01)
    assert flops.call_flops(PUBLISHED, 256) == 256 * 6_884_048


def test_call_bytes_hand_count():
    weights = 4 * (2 * (250 * 100 + 100) + 204 * 204 + 204 + 204 * 2 + 2)
    per_pair = 2 * 64 * (4 + 50 * 4) + 4 * 4 + 4
    assert flops.call_bytes(PUBLISHED, 0) == weights
    assert flops.call_bytes(PUBLISHED, 3) == weights + 3 * per_pair


@pytest.mark.parametrize("rows,bound", [(1, "bytes"), (256, "flops")])
def test_least_time_takes_the_larger_bound(rows, bound):
    p = peaks("TPU v5 lite")
    t, which = flops.least_time_s(PUBLISHED, rows, p["bf16_flops"],
                                  p["hbm_bytes_s"])
    assert which == bound
    assert t == max(flops.call_flops(PUBLISHED, rows) / 197e12,
                    flops.call_bytes(PUBLISHED, rows) / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
    assert peaks("TPU v5 lite")["hbm_bytes"] == 16e9
