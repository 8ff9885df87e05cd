"""Data-parallel ISGD engine: reduction contexts, shard_map parity with the
single-device reference, and the prefetching input pipeline.

The in-process tests run on however many devices this process has (1 under
the plain tier-1 invocation; 8 under the CI matrix entry that sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  The subprocess
test *always* exercises 8 devices by forcing the flag before jax init in a
child interpreter, so multi-device parity is covered on every run.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ISGDConfig, isgd_init, isgd_step
from repro.core.reduce import LOCAL, AxisReduce
from repro.data import FCPRSampler
from repro.distributed import (PrefetchSampler, make_data_parallel_step,
                               run_parity)
from repro.launch.mesh import make_data_mesh
from repro.optim import momentum, sgd
from repro.train.trainer import make_loss_and_grad

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# reduction contexts
# ---------------------------------------------------------------------------
def test_local_reduce_is_identity():
    lg = make_loss_and_grad(lambda p, b: (jnp.mean((p["w"] - b["t"]) ** 2),) * 2)
    wrapped = LOCAL.wrap_loss_and_grad(lg)
    assert wrapped is lg
    assert LOCAL.axis is None


def test_axis_reduce_means_over_mesh_axis():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_data_mesh()
    n = mesh.shape["data"]
    rctx = AxisReduce("data")
    x = jnp.arange(4 * n, dtype=jnp.float32)

    f = shard_map(lambda s: rctx.scalar(jnp.mean(s)), mesh=mesh,
                  in_specs=P("data"), out_specs=P(), check_vma=False)
    np.testing.assert_allclose(float(f(x)), float(jnp.mean(x)), rtol=1e-6)

    g = shard_map(lambda s: rctx.sum_scalar(jnp.sum(s)), mesh=mesh,
                  in_specs=P("data"), out_specs=P(), check_vma=False)
    np.testing.assert_allclose(float(g(x)), float(jnp.sum(x)), rtol=1e-6)

    # hashable + frozen: jit specializes without retracing per call
    assert hash(AxisReduce("data")) == hash(rctx)


def test_reduce_ctx_hashable_and_jit_specializes_without_retrace():
    """Every ReduceCtx flavor is a hashable static jit argument: equal
    contexts hit the jit cache (no retrace), distinct ones retrace once."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.reduce import StalenessReduce

    from repro.core.reduce import LocalReduce as _LR
    assert hash(LOCAL) == hash(_LR())
    assert hash(AxisReduce("data")) == hash(AxisReduce("data"))
    assert hash(StalenessReduce()) == hash(StalenessReduce())
    assert hash(StalenessReduce(decay="exp", alpha=0.5)) == \
        hash(StalenessReduce(decay="exp", alpha=0.5))
    assert StalenessReduce() == StalenessReduce(decay="inverse", alpha=1.0)
    assert StalenessReduce() != StalenessReduce(decay="exp")

    traces = []

    @partial(jax.jit, static_argnums=(0,))
    def step(ctx, x):
        traces.append(type(ctx).__name__)

        def lg(params, batch):
            loss = jnp.mean(params * batch)
            return (loss, loss), params
        (loss, _), g = ctx.wrap_loss_and_grad(lg)(x, x)
        return loss + jnp.sum(g)

    x = jnp.ones((4,), jnp.float32)
    step(LOCAL, x)
    step(LOCAL, x)                         # same ctx: cache hit
    step(_LR(), x)                         # fresh-but-equal ctx: cache hit
    assert traces == ["LocalReduce"]
    step(StalenessReduce(), x)
    step(StalenessReduce(decay="inverse", alpha=1.0), x)   # equal ⇒ cached
    assert traces == ["LocalReduce", "StalenessReduce"]
    step(StalenessReduce(decay="exp"), x)  # different ctx ⇒ one retrace
    assert traces == ["LocalReduce", "StalenessReduce", "StalenessReduce"]

    # AxisReduce's pmean needs its axis bound: count traces via shard_map
    mesh = make_data_mesh()
    ax_traces = []

    @partial(jax.jit, static_argnums=(0,))
    def ax_step(ctx, x):
        def inner(s):
            ax_traces.append(ctx.axis)
            return ctx.scalar(jnp.mean(s))
        return shard_map(inner, mesh=mesh, in_specs=P("data"), out_specs=P(),
                         check_vma=False)(x)

    n = mesh.shape["data"]
    xx = jnp.arange(4 * n, dtype=jnp.float32)
    ax_step(AxisReduce("data"), xx)
    ax_step(AxisReduce("data"), xx)        # equal ctx ⇒ no retrace
    assert ax_traces == ["data"]


# ---------------------------------------------------------------------------
# shard_map engine parity
# ---------------------------------------------------------------------------
def _parity_problem(batch_size, n_batches, dim=6, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(batch_size * n_batches, dim).astype(np.float32)
    ys = ((xs @ rng.randn(dim, 1).astype(np.float32)).ravel()
          / np.sqrt(dim)).astype(np.float32)
    ys[:batch_size] += 3.0     # outlier batch so the subproblem fires

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, loss

    params = {"w": jnp.zeros((dim,), jnp.float32),
              "b": jnp.zeros((), jnp.float32)}
    sampler = FCPRSampler({"x": xs, "y": ys}, batch_size=batch_size, seed=1)
    return loss_fn, params, sampler


def test_data_parallel_matches_reference_over_20_steps():
    """Tentpole invariant: params, ψ̄, control limit and the accelerate
    decision agree with the single-device step across ≥20 steps."""
    n_dev = len(jax.devices())
    loss_fn, params0, sampler = _parity_problem(batch_size=8 * n_dev,
                                                n_batches=4)
    rule = momentum(0.9)
    icfg = ISGDConfig(n_batches=sampler.n_batches, k_sigma=1.0, stop=3,
                      zeta=0.01)
    lg = make_loss_and_grad(loss_fn)
    ref_step = jax.jit(lambda s, p, b: isgd_step(rule, icfg, lg, s, p, b, 0.01))
    mesh = make_data_mesh()
    init_fn, dp_step = make_data_parallel_step(
        loss_fn, rule, icfg, mesh, lr_fn=lambda _: jnp.asarray(0.01))

    ref_p = jax.tree.map(jnp.copy, params0)
    ref_s = isgd_init(rule, icfg, ref_p)
    dp_p = jax.tree.map(jnp.copy, params0)
    dp_s = init_fn(dp_p)

    accels = 0
    for j in range(22):
        batch = {k: jnp.asarray(v) for k, v in sampler(j).items()}
        ref_s, ref_p, mr = ref_step(ref_s, ref_p, batch)
        dp_s, dp_p, md = dp_step(dp_s, dp_p, batch)
        for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(dp_p)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(float(mr["psi_bar"]), float(md["psi_bar"]),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(float(mr["limit"]), float(md["limit"]),
                                   atol=1e-5, rtol=1e-5)
        assert bool(mr["accelerated"]) == bool(md["accelerated"])
        accels += int(bool(mr["accelerated"]))
    assert accels > 0, "subproblem never fired; cond path untested"
    assert int(dp_s.accel_count) == accels


def test_data_parallel_consistent_step_runs():
    n_dev = len(jax.devices())
    loss_fn, params0, sampler = _parity_problem(batch_size=8 * n_dev,
                                                n_batches=2)
    icfg = ISGDConfig(n_batches=2)
    mesh = make_data_mesh()
    init_fn, step = make_data_parallel_step(
        loss_fn, sgd(), icfg, mesh, inconsistent=False,
        lr_fn=lambda _: jnp.asarray(0.05))
    p = jax.tree.map(jnp.copy, params0)
    s = init_fn(p)
    for j in range(3):
        batch = {k: jnp.asarray(v) for k, v in sampler(j).items()}
        s, p, m = step(s, p, batch)
    assert not bool(m["accelerated"])
    assert np.isfinite(float(m["loss"]))


def test_run_parity_inprocess():
    r = run_parity(steps=20, tol=1e-5)
    assert r["ok"], r
    assert r["accelerations"] > 0


def test_parity_subprocess_8_devices():
    """The acceptance-criteria check: 8 forced host devices, 20 steps,
    1e-5 agreement, accelerate branch identical — in a fresh interpreter so
    the device count doesn't leak into this process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)     # parity sets the device-count flag itself
    proc = subprocess.run(
        [sys.executable, "-m", "repro.distributed.parity",
         "--devices", "8", "--steps", "20", "--tol", "1e-5"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "devices=8" in proc.stdout


# ---------------------------------------------------------------------------
# prefetch pipeline
# ---------------------------------------------------------------------------
def test_prefetch_preserves_fcpr_batches():
    _, _, sampler = _parity_problem(batch_size=8, n_batches=3)
    pf = PrefetchSampler(sampler, depth=2)
    assert (pf.n_batches, pf.batch_size) == (sampler.n_batches, 8)
    for j in range(7):          # wraps the cycle twice
        got = pf(j)
        want = sampler(j)
        assert pf.batch_index(j) == sampler.batch_index(j)
        for k in want:
            assert isinstance(got[k], jax.Array)
            np.testing.assert_array_equal(np.asarray(got[k]), want[k])


def test_prefetch_stages_ahead_and_handles_random_access():
    _, _, sampler = _parity_problem(batch_size=8, n_batches=4)
    pf = PrefetchSampler(sampler, depth=2)
    pf(0)
    assert 1 in pf._staged                 # next batch already in flight
    got = pf(3)                            # random access: cold miss
    np.testing.assert_array_equal(np.asarray(got["y"]), sampler(3)["y"])
    assert all(k > 3 for k in pf._staged)  # stale entries dropped


def test_prefetch_with_mesh_sharding_feeds_dp_step():
    from repro.launch.shardings import data_parallel_shardings

    mesh = make_data_mesh()
    n_dev = mesh.shape["data"]
    loss_fn, params0, sampler = _parity_problem(batch_size=4 * n_dev,
                                                n_batches=2)
    # per-leaf sharding dict (launch path) — same layout as the blanket one
    shs = data_parallel_shardings(mesh, sampler(0))
    assert set(shs) == set(sampler(0))
    for s in shs.values():      # batch dim over 'data', rest unsharded
        assert s.spec[0] == "data" and all(a is None for a in s.spec[1:])
    pf = PrefetchSampler(sampler, sharding=shs)
    icfg = ISGDConfig(n_batches=2)
    init_fn, step = make_data_parallel_step(
        loss_fn, sgd(), icfg, mesh, lr_fn=lambda _: jnp.asarray(0.05))
    p = jax.tree.map(jnp.copy, params0)
    s = init_fn(p)
    s, p, m = step(s, p, pf(0))
    assert np.isfinite(float(m["loss"]))
