"""Scheduler parity matrix: the scheduled engines vs the hard-wired ones.

Two contracts from the ``repro.sched`` package doc are pinned here, both
driven by a **ψ̄-dependent** ``lr_fn`` (so any schedule-induced drift in the
control statistics breaks the comparison loudly):

  * **FCPR bit-exactness** — threading :class:`FCPRSchedule` through the
    scheduled engines reproduces the pre-scheduler engines EXACTLY:
    per-step vs ``make_train_step`` (host batches), chunked K ∈ {1, 32} vs
    the per-step reference, and the data-parallel per-step + chunked K=4
    legs vs the hard-wired shard_map engine (the hybrid strategies get the
    same treatment in ``repro.distributed.hybrid_parity``);
  * **replicated-deterministic selection** — under ``loss-prop`` every
    data shard draws the same batch index at every step: checked directly
    (a shard_map stacking each shard's draw over the data axis must be
    constant) and end-to-end (the n-device chunked run reproduces the
    1-device run's visit sequence);

plus the device-residency invariant: the chunked ``loss-prop`` engine makes
exactly ``steps / K`` host dispatches — selection, table update and batch
fetch all live inside the fused scan (metrics, including the realized
``batch_idx`` sequence, come back (K,)-stacked in one transfer per chunk).

Usable two ways (same pattern as ``repro.distributed.parity``):

  * in-process: ``run_sched_parity()`` on whatever devices exist;
  * subprocess with a forced device count (the CI acceptance check):

      PYTHONPATH=src python -m repro.sched.parity --devices 8
"""
from __future__ import annotations

import argparse
import os
import sys


def _force_host_devices(n: int) -> None:
    assert "jax" not in sys.modules, "--devices must be set before jax init"
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())


def run_sched_parity(steps: int = 32, verbose: bool = False) -> dict:
    """Returns {"ok": bool, "devices": int, "legs": {name: report}, ...}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import ISGDConfig
    from repro.data import DeviceRing, FCPRSampler
    from repro.distributed import (make_chunked_data_parallel_step,
                                   make_data_parallel_step)
    from repro.launch.mesh import make_data_mesh
    from repro.optim import momentum
    from repro.sched import FCPRSchedule, LossPropSchedule
    from repro.train import (make_chunked_train_step,
                             make_scheduled_train_step, make_train_step)

    n_dev = len(jax.devices())
    n_batches = 4
    batch_size = 8 * n_dev
    assert steps % 32 == 0 and steps >= 2 * n_batches

    # dim=6: the repo's canonical bit-exact problem size (XLA:CPU compiles
    # straight-line and in-scan step bodies to identical float programs
    # there; wider dims pick up 1-ulp fusion differences)
    dim = 6
    rng = np.random.RandomState(0)
    xs = rng.randn(batch_size * n_batches, dim).astype(np.float32)
    ys = ((xs @ rng.randn(dim, 1).astype(np.float32)).ravel()
          / np.sqrt(dim)).astype(np.float32)
    ys[:batch_size] += 3.0                      # the under-trained batch
    sampler = FCPRSampler({"x": xs, "y": ys}, batch_size=batch_size, seed=1)

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, loss

    params0 = {"w": jnp.zeros((dim,), jnp.float32),
               "b": jnp.zeros((), jnp.float32)}
    rule = momentum(0.9)
    icfg = ISGDConfig(n_batches=n_batches, k_sigma=1.0, stop=3, zeta=0.01)

    def lr_fn(psi_bar):
        # ψ̄-dependent on purpose: schedule drift moves the LR trajectory
        return jnp.asarray(0.01) + 0.001 * jnp.minimum(psi_bar, 1.0)

    def drive(step_fn, init_fn, feed):
        p = jax.tree.map(jnp.copy, params0)
        s = init_fn(p)
        ms = []
        for j in range(steps):
            s, p, m = step_fn(s, p, feed(j))
            ms.append(jax.tree.map(np.asarray, m))
        return s, p, {k: np.stack([m[k] for m in ms]) for k in ms[0]}

    def drive_sched(fn, init_fn, schedule, ring, K=None):
        p = jax.tree.map(jnp.copy, params0)
        s = init_fn(p)
        ss = schedule.init(n_batches)
        out = []
        if K is None:
            for j in range(steps):
                s, p, ss, m = fn(s, p, ss, ring.arrays, j)
                out.append(jax.tree.map(np.asarray, m))
            return s, p, {k: np.stack([m[k] for m in out]) for k in out[0]}
        for c in range(steps // K):
            s, p, ss, ms = fn(s, p, ss, ring.arrays, c * K)
            out.append(jax.tree.map(np.asarray, ms))
        return s, p, {k: np.concatenate([o[k] for o in out])
                      for k in out[0]}

    def bit_exact(ref, got):
        r_s, _, r_m = ref
        g_s, _, g_m = got
        ok = all(bool(np.array_equal(r_m[k], g_m[k]))
                 for k in ("loss", "limit", "psi_bar", "accelerated",
                           "sub_iters"))
        dev = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                  for a, b in zip(jax.tree.leaves(ref[1]),
                                  jax.tree.leaves(got[1])))
        ok &= dev == 0.0
        ok &= int(r_s.accel_count) == int(g_s.accel_count)
        return ok, dev

    legs = {}
    fcpr = FCPRSchedule()
    host = [{k: jnp.asarray(v) for k, v in sampler(j).items()}
            for j in range(steps)]

    # reference: per-step engine on host batches
    init_fn, step = make_train_step(loss_fn, rule, icfg, lr_fn=lr_fn,
                                    donate=False)
    ref = drive(step, init_fn, lambda j: host[j])
    assert ref[2]["accelerated"].sum() > 0, "subproblem never fired"

    ring = DeviceRing(sampler.epoch_arrays(), batch_size)
    sinit, sstep = make_scheduled_train_step(loss_fn, rule, icfg, fcpr,
                                             lr_fn=lr_fn, donate=False)
    ok, dev = bit_exact(ref, drive_sched(sstep, sinit, fcpr, ring))
    legs["sched-fcpr per-step"] = {"ok": ok, "max_param": dev}

    for K in (1, 32):
        cinit, chunk = make_chunked_train_step(
            loss_fn, rule, icfg, chunk_steps=K, lr_fn=lr_fn, donate=False,
            schedule=fcpr)
        ok, dev = bit_exact(ref, drive_sched(chunk, cinit, fcpr, ring, K=K))
        legs[f"sched-fcpr chunked K{K}"] = {"ok": ok, "max_param": dev}

    # data-parallel engine legs (manual shard_map strategy)
    mesh = make_data_mesh()
    dinit, dstep = make_data_parallel_step(loss_fn, rule, icfg, mesh,
                                           lr_fn=lr_fn, donate=False)
    dp = drive(dstep, dinit, lambda j: host[j])
    ring_m = DeviceRing(sampler.epoch_arrays(), batch_size, mesh=mesh)
    sinit, sstep = make_data_parallel_step(loss_fn, rule, icfg, mesh,
                                           lr_fn=lr_fn, donate=False,
                                           schedule=fcpr)
    ok, dev = bit_exact(dp, drive_sched(sstep, sinit, fcpr, ring_m))
    legs["sched-fcpr dp per-step"] = {"ok": ok, "max_param": dev}

    cinit, chunk = make_chunked_data_parallel_step(
        loss_fn, rule, icfg, mesh, chunk_steps=4, lr_fn=lr_fn, donate=False,
        schedule=fcpr)
    ok, dev = bit_exact(dp, drive_sched(chunk, cinit, fcpr, ring_m, K=4))
    legs["sched-fcpr dp chunked K4"] = {"ok": ok, "max_param": dev}

    # loss-prop: per-shard draws must agree at every step (direct check)
    lp = LossPropSchedule(eps=0.2)

    def draws(table, visits, step_arr):
        # each shard draws from the same (replicated) state and step index
        key = jax.random.fold_in(jax.random.PRNGKey(0), step_arr)
        t, _ = lp.select({"table": table, "visits": visits}, step_arr, key)
        return t[None]

    per_shard = shard_map(draws, mesh=mesh, in_specs=(P(), P(), P()),
                          out_specs=P("data"), check_vma=False)
    table = jnp.asarray(rng.rand(n_batches).astype(np.float32)) * 3.0
    visits = jnp.ones((n_batches,), jnp.int32)
    agree = True
    for j in range(n_batches, n_batches + 16):      # post-warm-up draws
        t = np.asarray(per_shard(table, visits, jnp.asarray(j, jnp.int32)))
        agree &= bool((t == t[0]).all())
    legs["loss-prop shard-draw agreement"] = {"ok": agree, "max_param": None}

    # loss-prop: n-device chunked run == 1-device run (selection + ψ)
    K = 8

    def lp_run(mesh_, ring_):
        maker = (make_chunked_data_parallel_step if mesh_ is not None
                 else None)
        if mesh_ is None:
            cinit, chunk = make_chunked_train_step(
                loss_fn, rule, icfg, chunk_steps=K, lr_fn=lr_fn,
                donate=False, schedule=lp)
        else:
            cinit, chunk = maker(loss_fn, rule, icfg, mesh_, chunk_steps=K,
                                 lr_fn=lr_fn, donate=False, schedule=lp)
        calls = [0]
        def counting(*a):
            calls[0] += 1
            return chunk(*a)
        out = drive_sched(counting, cinit, lp, ring_, K=K)
        return out, calls[0]

    one, calls1 = lp_run(None, ring)
    many, calls_n = lp_run(mesh, ring_m)
    same_idx = bool(np.array_equal(one[2]["batch_idx"],
                                   many[2]["batch_idx"]))
    # ψ agrees to reduction-reassociation tolerance (f32 pmean vs global)
    finite = np.isfinite(one[2]["loss"])
    close = bool(np.allclose(one[2]["loss"][finite],
                             many[2]["loss"][finite], atol=1e-5, rtol=1e-5))
    legs["loss-prop 1-vs-n-device selection"] = {
        "ok": same_idx and close, "max_param": None}

    # device residency: one host dispatch per K-step chunk, no per-step sync
    legs["loss-prop dispatches = steps/K"] = {
        "ok": calls1 == steps // K and calls_n == steps // K,
        "max_param": None}
    legs["loss-prop visits all batches"] = {
        "ok": bool((np.bincount(one[2]["batch_idx"],
                                minlength=n_batches) > 0).all()),
        "max_param": None}

    ok = all(leg["ok"] for leg in legs.values())
    if verbose:
        for name, leg in legs.items():
            print(f"  {name:34s} ok={leg['ok']} "
                  f"max_param={leg['max_param']}")
    return {"ok": ok, "devices": n_dev, "steps": steps,
            "accelerations": int(ref[2]["accelerated"].sum()), "legs": legs}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=0,
                    help="force this many XLA host-platform devices "
                         "(0 = use whatever XLA_FLAGS already provides)")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    if args.devices:
        _force_host_devices(args.devices)
    r = run_sched_parity(steps=args.steps, verbose=args.verbose)
    bad = [n for n, leg in r["legs"].items() if not leg["ok"]]
    print(f"sched-parity devices={r['devices']} steps={r['steps']} "
          f"accelerations={r['accelerations']} legs={len(r['legs'])} "
          f"failed={bad or 'none'} -> {'OK' if r['ok'] else 'FAIL'}")
    if r["accelerations"] == 0:
        print("sched-parity WARNING: subproblem never fired")
        return 2
    return 0 if r["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
