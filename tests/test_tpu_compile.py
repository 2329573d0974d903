"""The served path's kernels compile for a TPU v5e at sm-cnn's published
widths.

Nothing runs: the TPU compiler builds each program for a v5e chip that is
described, not attached, so these tests catch what interpret mode cannot
(tile alignment, fast-memory limits) without a chip. The topology is
described only inside the module fixture — the TPU library admits one
process at a time, and describing it at import would make every test
worker try.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.kernels.sm_cnn_conv import conv_tanh_maxpool
from repro.models import sm_cnn

CFG = get_config("sm-cnn")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _score_args(batch, sharding):
    params = jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, sharding),
        jax.eval_shape(lambda k: sm_cnn.init_sm_cnn(k, CFG),
                       jax.random.PRNGKey(0)))
    tok = _spec((batch, CFG.max_len), jnp.int32, sharding)
    feats = _spec((batch, CFG.n_extra_feats), jnp.float32, sharding)
    return params, tok, tok, feats


@pytest.mark.parametrize("batch", [1, 8, 64, 256])
def test_conv_kernel_compiles_at_published_widths(one_chip, batch):
    x = _spec((batch, CFG.max_len, CFG.embed_dim), jnp.float32, one_chip)
    w = _spec((CFG.filter_width * CFG.embed_dim, CFG.conv_filters),
              jnp.float32, one_chip)
    b = _spec((CFG.conv_filters,), jnp.float32, one_chip)
    fn = jax.jit(functools.partial(conv_tanh_maxpool, width=CFG.filter_width,
                                   interpret=False))
    compiled = fn.lower(x, w, b).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [1, 64])
@pytest.mark.parametrize("backend", ["pallas", "jit"])
def test_scorer_compiles_at_published_widths(one_chip, backend, batch):
    if backend == "pallas":
        fn = functools.partial(kops.sm_cnn_score, cfg=CFG, interpret=False)
    else:
        fn = functools.partial(sm_cnn.score, cfg=CFG)
    compiled = jax.jit(fn).lower(*_score_args(batch, one_chip)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")
