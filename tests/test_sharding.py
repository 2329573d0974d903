"""Sharding rules + roofline machinery unit tests (AbstractMesh: no devices
needed — the full-mesh behaviour is covered by the dry-run artifacts)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed import sharding as SH
from repro.roofline.analysis import model_bytes, model_flops
from repro.roofline.hlo_parse import shape_bytes, split_computations

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _lm_tree():
    return {
        "embed": jax.ShapeDtypeStruct((102400, 2048), jnp.bfloat16),
        "lm_head": jax.ShapeDtypeStruct((2048, 102400), jnp.bfloat16),
        "layers": {
            "attn": {"wq": jax.ShapeDtypeStruct((28, 2048, 2048), jnp.bfloat16),
                     "wo": jax.ShapeDtypeStruct((28, 2048, 2048), jnp.bfloat16)},
            "attn_norm": jax.ShapeDtypeStruct((28, 2048), jnp.bfloat16),
            "mlp": {"w_gate": jax.ShapeDtypeStruct((28, 2048, 11264), jnp.bfloat16),
                    "w_down": jax.ShapeDtypeStruct((28, 11264, 2048), jnp.bfloat16)},
            "moe": {"w_gate": jax.ShapeDtypeStruct((28, 64, 2048, 1408), jnp.bfloat16),
                    "router": jax.ShapeDtypeStruct((28, 2048, 64), jnp.float32)},
        },
    }


def test_lm_tp_specs():
    specs = SH.param_specs(_lm_tree(), "lm", MESH)
    assert specs["embed"] == P("model", None)
    assert specs["layers"]["attn"]["wq"] == P(None, None, "model")
    assert specs["layers"]["attn"]["wo"] == P(None, "model", None)
    assert specs["layers"]["moe"]["w_gate"] == P(None, "model", None, None)
    assert specs["layers"]["moe"]["router"] == P(None, None, None)
    assert specs["layers"]["attn_norm"] == P(None, None)


def test_lm_fsdp_specs():
    specs = SH.param_specs(_lm_tree(), "lm_fsdp", MESH)
    # matrices shard their largest divisible dim over ALL axes
    assert specs["layers"]["mlp"]["w_gate"] == P(None, None, ("data", "model"))
    # vocab tensors stay model-aligned for the logits contract
    assert specs["embed"] == P("model", None)
    assert specs["layers"]["attn_norm"] == P(None, None)


def test_zero_shard_extends_unsharded_dim():
    spec = SH.zero_shard_spec(P(None, None, "model"), (28, 2048, 11264), MESH)
    assert spec == P(None, "data", "model")
    # no double-use of the data axis
    spec2 = SH.zero_shard_spec(P(("data", "model"), None), (1024, 64), MESH)
    assert spec2 == P(("data", "model"), None)


def test_recsys_table_specs():
    tree = {"emb": jax.ShapeDtypeStruct((187768320, 128), jnp.bfloat16),
            "bot": {"w": [jax.ShapeDtypeStruct((13, 512), jnp.bfloat16)]}}
    specs = SH.param_specs(tree, "recsys", MESH3)
    assert specs["emb"] == P(("pod", "data", "model"), None)
    assert specs["bot"]["w"][0] == P(None, None)


def test_cache_specs_shard_sequence_over_model():
    cache = {"k": jax.ShapeDtypeStruct((28, 128, 32768, 8, 128), jnp.bfloat16)}
    specs = SH.cache_specs(cache, None, MESH)
    assert specs["k"] == P(None, "data", "model", None, None)


# --- roofline helpers --------------------------------------------------------

def test_shape_bytes():
    assert shape_bytes("f32[8,128]{1,0}") == 8 * 128 * 4
    assert shape_bytes("bf16[4,4]") == 32
    assert shape_bytes("(f32[2,2], s32[3])") == 16 + 12
    assert shape_bytes("pred[7]") == 7


def test_split_computations_parses_entry():
    hlo = """HloModule m

%helper (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  ROOT %b = f32[4]{0} add(%a, %a)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %y = f32[4]{0} call(%x), to_apply=%helper
}
"""
    comps, entry = split_computations(hlo)
    assert entry == "main"
    assert "helper" in comps
    assert comps["helper"].ops[-1].opcode == "add"


@pytest.mark.parametrize("arch,shape", [
    ("qwen3-0.6b", "train_4k"), ("deepseek-moe-16b", "decode_32k"),
    ("meshgraphnet", "ogb_products"), ("dlrm-mlperf", "train_batch"),
    ("fm", "retrieval_cand"), ("bert4rec", "serve_bulk"),
])
def test_model_flops_and_bytes_positive(arch, shape):
    assert model_flops(arch, shape) > 0
    assert model_bytes(arch, shape) > 0


def test_moe_active_flops_less_than_total():
    from repro.configs import get_config
    cfg = get_config("deepseek-moe-16b")
    assert cfg.n_active_params() < cfg.n_params() / 3
