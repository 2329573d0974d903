"""Entry-point plumbing: config names, the compile-cache rule, and the
chip smoke's refusal to run anywhere but on a TPU."""
import json
import math
import os
import subprocess
import sys

import pytest

from repro.configs import get_config, reduced
from repro.launch.serve import build_parser

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env_extra=None, drop=(), timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               **(env_extra or {}))
    args = (code_or_args if isinstance(code_or_args, list)
            else ["-c", code_or_args])
    return subprocess.run([sys.executable] + args, env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_names_the_reduced_twin():
    assert get_config("sm-cnn-smoke") == reduced(get_config("sm-cnn"))
    assert get_config("sm-cnn").embed_dim == 50
    with pytest.raises(KeyError):
        get_config("no-such-smoke")


def test_serve_names_its_config():
    assert build_parser().parse_args([]).config == "sm-cnn-smoke"
    args = build_parser().parse_args(["--config", "sm-cnn"])
    assert get_config(args.config).conv_filters == 100


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print("DIR", enable_compile_cache(), jax.config.jax_compilation_cache_dir)
"""


def test_cache_dir_from_the_environment_stays_in_force(tmp_path):
    out = _run(_CACHE_PROBE + "jax.jit(lambda x: x * 2)(jnp.ones(3))",
               {"JAX_ENABLE_COMPILATION_CACHE": "true",
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    assert f"DIR {tmp_path} {tmp_path}" in out.stdout, out.stderr
    assert any(tmp_path.iterdir())          # the compile landed there


def test_cache_defaults_to_the_checkout_directory():
    out = _run(_CACHE_PROBE, {"JAX_ENABLE_COMPILATION_CACHE": "true"},
               drop=("JAX_COMPILATION_CACHE_DIR",))
    want = os.path.join(ROOT, ".jax_cache")
    assert f"DIR {want} {want}" in out.stdout, out.stderr


def test_tests_run_with_the_cache_off():
    # conftest switches it off for this process and everything it spawns.
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    out = _run(_CACHE_PROBE, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert "DIR None None" in out.stdout, out.stderr


def test_chip_smoke_refuses_the_cpu():
    out = _run(["chip_smoke.py"], timeout=300)
    assert out.returncode != 0
    assert "FAILED" in out.stderr and "need 'tpu'" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.fixture(scope="module")
def chip_smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _shifted(p, dlogit):
    return float(1 / (1 + (1 - p) / p * math.exp(-dlogit)))


@pytest.mark.parametrize("shift,caught", [(0.0, False), (0.05, False),
                                          (0.3, True), (-0.3, True)])
def test_chip_smoke_compares_saturated_scores_as_logits(chip_smoke, shift,
                                                        caught):
    # Near-saturated scores: a logit shift of 0.3 moves P by under 1e-2,
    # which a check on P alone would let through.
    want = {"q": [(0, i, p) for i, p in enumerate((0.999, 0.99, 0.02,
                                                   0.001))]}
    got = {"q": [(d, s, _shifted(p, shift)) for d, s, p in want["q"]]}
    assert max(abs(g[2] - w[2]) for g, w in zip(got["q"], want["q"])) < 1e-2
    if caught:
        with pytest.raises(SystemExit, match="FAILED"):
            chip_smoke.check_rankings("t", got, want, 4, 0.1)
    else:
        identical, _, max_dl = chip_smoke.check_rankings("t", got, want, 4,
                                                         0.1)
        assert identical == 1 and max_dl == pytest.approx(shift, abs=1e-6)


def test_chip_smoke_logit_clips_saturated_scores(chip_smoke):
    lo, hi = chip_smoke.SCORE_CLIP, 1 - chip_smoke.SCORE_CLIP
    assert chip_smoke.logit([0.0, 1.0]) == pytest.approx(
        chip_smoke.logit([lo, hi]))
    assert chip_smoke.logit(0.5) == pytest.approx(0.0)
