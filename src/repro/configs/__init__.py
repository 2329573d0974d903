"""Architecture registry: ``--arch <id>`` resolves here.

Every assigned architecture (plus the paper's own sm-cnn) registers its full
config and its shape set. ``get_config``/``get_shapes``/``cells`` are the
single source of truth for smoke tests, the dry-run, and the roofline table.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

from repro.configs.base import (  # noqa: F401
    CRITEO_VOCABS, GNNConfig, GNN_SHAPES, LMConfig, LM_SHAPES, MoESpec,
    RecsysConfig, RECSYS_SHAPES, ShapeSpec, TextPairConfig, TEXTPAIR_SHAPES,
    reduced,
)

_MODULES = {
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "moonshot-v1-16b-a3b": "repro.configs.moonshot_v1_16b_a3b",
    "qwen3-0.6b": "repro.configs.qwen3_0_6b",
    "deepseek-coder-33b": "repro.configs.deepseek_coder_33b",
    "granite-3-2b": "repro.configs.granite_3_2b",
    "meshgraphnet": "repro.configs.meshgraphnet",
    "bert4rec": "repro.configs.bert4rec",
    "fm": "repro.configs.fm",
    "dlrm-mlperf": "repro.configs.dlrm_mlperf",
    "din": "repro.configs.din",
    "sm-cnn": "repro.configs.sm_cnn",
}

ASSIGNED_ARCHS = tuple(a for a in _MODULES if a != "sm-cnn")


def get_config(arch: str):
    """The published config of ``arch``; ``<arch>-smoke`` names its
    ``reduced`` twin (the tiny same-family config CPU tests run)."""
    if arch.endswith("-smoke") and arch[:-len("-smoke")] in _MODULES:
        return reduced(get_config(arch[:-len("-smoke")]))
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch]).CONFIG


def get_shapes(arch: str) -> Tuple[ShapeSpec, ...]:
    return tuple(importlib.import_module(_MODULES[arch]).SHAPES)


def shape_applicable(cfg, shape: ShapeSpec) -> Tuple[bool, str]:
    """Whether a (arch, shape) cell is runnable, and if not, why (skip note)."""
    if getattr(cfg, "family", "") == "lm" and shape.kind == "long_decode":
        if not cfg.sub_quadratic:
            return False, ("pure full-attention arch: 512k-token KV decode is "
                           "skipped per assignment rule (needs sub-quadratic "
                           "attention); see DESIGN.md §Arch-applicability")
    return True, ""


def cells(include_inapplicable: bool = False) -> List[Tuple[str, ShapeSpec]]:
    """All assigned (arch, shape) cells (40 incl. skipped long_500k rows)."""
    out = []
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in get_shapes(arch):
            ok, _ = shape_applicable(cfg, shape)
            if ok or include_inapplicable:
                out.append((arch, shape))
    return out
