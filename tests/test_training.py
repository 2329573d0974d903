"""Optimizer / checkpoint / fault-tolerance / compression tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import compression as C
from repro.training import fault_tolerance as FT
from repro.training.checkpoint import CheckpointManager
from repro.training.optimizer import (adamw, clip_by_global_norm, global_norm,
                                      sgd, warmup_cosine_schedule)


def _quadratic_converges(opt, steps=300, tol=1e-2):
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros((3,))}
    st = opt.init(params)
    loss_fn = lambda p: jnp.sum((p["w"] - target) ** 2)  # noqa: E731
    for _ in range(steps):
        g = jax.grad(loss_fn)(params)
        params, st = opt.update(params, g, st)
    assert float(loss_fn(params)) < tol, float(loss_fn(params))


def test_adamw_converges():
    _quadratic_converges(adamw(3e-2))


def test_sgd_converges():
    _quadratic_converges(sgd(5e-2, momentum=0.9))


def test_adamw_mixed_precision_masters():
    """bf16 params keep fp32 masters: tiny updates must not be lost."""
    opt = adamw(1e-4, clip_norm=None)
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    st = opt.init(params)
    for _ in range(50):
        g = {"w": jnp.full((4,), 1e-3, jnp.bfloat16)}
        params, st = opt.update(params, g, st)
    # fp32 master moved even though each bf16 step would round to nothing
    assert float(st["master"]["w"][0]) < 1.0
    assert params["w"].dtype == jnp.bfloat16


def test_clip_by_global_norm():
    tree = {"a": jnp.full((10,), 10.0)}
    clipped, gn = clip_by_global_norm(tree, 1.0)
    assert float(gn) > 1.0
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)


def test_warmup_cosine_schedule():
    s = warmup_cosine_schedule(1.0, warmup=10, total=100)
    assert float(s(jnp.asarray(0))) == 0.0
    np.testing.assert_allclose(float(s(jnp.asarray(10))), 1.0, rtol=1e-6)
    assert float(s(jnp.asarray(100))) < float(s(jnp.asarray(50)))


def test_checkpoint_atomic_keep_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    params = {"w": jnp.arange(4.0)}
    for step in (10, 20, 30):
        mgr.save(step, params)
    assert mgr.list_steps() == [20, 30]
    p2, _, step = mgr.restore({"w": jnp.zeros(4)})
    assert step == 30
    np.testing.assert_allclose(p2["w"], params["w"])


def test_checkpoint_restores_optimizer_state(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    opt = adamw(1e-2)
    params = {"w": jnp.ones((3,))}
    st = opt.init(params)
    params, st = opt.update(params, {"w": jnp.ones((3,))}, st)
    mgr.save(5, params, st)
    p2, st2, _ = mgr.restore(params, st)
    assert int(st2["step"]) == 1
    np.testing.assert_allclose(st2["mu"]["w"], st["mu"]["w"])


def test_retry_step_recovers():
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return x + 1

    assert FT.retry_step(flaky, 1, max_retries=3) == 2
    assert calls["n"] == 3


def test_retry_step_gives_up():
    def dead(_):
        raise RuntimeError("hard failure")
    with pytest.raises(FT.StepFailure):
        FT.retry_step(dead, 0, max_retries=2)


def test_straggler_monitor_flags_outliers():
    mon = FT.StragglerMonitor(threshold=2.0, warmup_steps=3)
    for i in range(10):
        mon.record(i, 0.1)
    assert mon.record(10, 0.5) is True
    assert mon.record(11, 0.1) is False


def test_elastic_mesh_planning():
    assert FT.plan_elastic_mesh(256, 16) == (16, 16)
    assert FT.plan_elastic_mesh(240, 16) == (8, 16)   # lost a host: degrade
    with pytest.raises(ValueError):
        FT.plan_elastic_mesh(8, 16)


def test_scale_batch_for_mesh():
    assert FT.scale_batch_for_mesh(256, 16, 8, keep_global=True) == 256
    assert FT.scale_batch_for_mesh(256, 16, 8, keep_global=False) == 128


def test_compression_error_feedback_unbiased_over_time():
    """With error feedback, the accumulated applied signal tracks the true
    gradient sum (residual stays bounded)."""
    g = {"w": jnp.linspace(-0.3, 0.7, 64)}
    err = C.init_error_feedback(g)
    applied = jnp.zeros((64,))
    for _ in range(40):
        q, s, err = C.compress_with_feedback(g, err)
        applied = applied + C.decompress(q, s)["w"]
    truth = g["w"] * 40
    err_norm = float(jnp.abs(applied - truth).max())
    scale = float(s["w"])
    assert err_norm <= scale + 1e-6  # residual bounded by one quantum


def test_compressed_psum_matches_mean(monkeypatch):
    """shard_map int8 psum ≈ the fp32 mean within quantization error."""
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs, ("d",))
    g = {"w": jnp.linspace(-1, 1, 8)[None, :]}
    err = {"w": jnp.zeros((1, 8))}

    def f(g, e):
        return C.compressed_psum(g, e, "d")

    out, _ = jax.shard_map(f, mesh=mesh, in_specs=(P("d"), P("d")),
                       out_specs=(P("d"), P("d")))(g, err)
    np.testing.assert_allclose(np.asarray(out["w"][0]),
                               np.asarray(g["w"][0]), atol=2e-2)
