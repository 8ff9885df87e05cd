"""Fused multi-step engine + device-resident FCPR ring.

Acceptance invariants for the chunked trainer (ISSUE 2):

  * **bit-exact parity** — the ``lax.scan`` engine reproduces the per-step
    engine's losses, control limits, accelerate decisions, sub-iteration
    counts and final params EXACTLY (``assert_array_equal``, not allclose)
    for K ∈ {1, 4, 32} over ≥ 2 FCPR epochs, single-device and (under the
    CI matrix's XLA_FLAGS) 8 forced devices;
  * **ring equivalence** — a ``DeviceRing`` serves bit-identical batches to
    the host ``FCPRSampler`` across epoch wrap-around, in both unsharded
    and mesh-sharded layouts, and ``ring_or_prefetch`` degrades to the
    ``PrefetchSampler`` (same batches) when the epoch busts the byte budget.

The ψ̄-dependent ``lr_fn`` below is deliberate: it makes the loss-driven LR
read the *previous* step's queue, so any off-by-one in how the scan carries
the queue breaks parity loudly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ISGDConfig
from repro.data import DeviceRing, FCPRSampler, ring_or_prefetch
from repro.data.device_ring import _shard_layout
from repro.distributed import (PrefetchSampler,
                               make_chunked_data_parallel_step,
                               make_data_parallel_step)
from repro.launch.mesh import make_data_mesh
from repro.optim import momentum
from repro.train import TrainLog, make_chunked_train_step, make_train_step

STEPS = 32                      # n_batches=4 -> 8 FCPR epochs


def _problem(batch_size, n_batches=4, dim=6, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(batch_size * n_batches, dim).astype(np.float32)
    ys = ((xs @ rng.randn(dim, 1).astype(np.float32)).ravel()
          / np.sqrt(dim)).astype(np.float32)
    ys[:batch_size] += 3.0      # outlier batch: the subproblem must fire

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, loss

    params = {"w": jnp.zeros((dim,), jnp.float32),
              "b": jnp.zeros((), jnp.float32)}
    sampler = FCPRSampler({"x": xs, "y": ys}, batch_size=batch_size, seed=1)
    icfg = ISGDConfig(n_batches=sampler.n_batches, k_sigma=1.0, stop=3,
                      zeta=0.01)
    return loss_fn, params, sampler, icfg


def _lr_fn(psi_bar):
    # ψ̄-dependent on purpose: catches queue-lag regressions (see module doc)
    return jnp.asarray(0.01) + 0.001 * jnp.minimum(psi_bar, 1.0)


def _run_per_step(step_fn, init_fn, params0, feed, steps):
    p = jax.tree.map(jnp.copy, params0)
    s = init_fn(p)
    ms = []
    for j in range(steps):
        s, p, m = step_fn(s, p, feed(j))
        ms.append(jax.tree.map(np.asarray, m))
    stacked = {k: np.stack([m[k] for m in ms]) for k in ms[0]}
    return s, p, stacked


def _run_chunked(chunk_fn, init_fn, params0, ring_arrays, steps, K):
    assert steps % K == 0
    p = jax.tree.map(jnp.copy, params0)
    s = init_fn(p)
    outs = []
    for c in range(steps // K):
        s, p, ms = chunk_fn(s, p, ring_arrays, c * K)
        outs.append(jax.tree.map(np.asarray, ms))
    stacked = {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}
    return s, p, stacked


def _assert_bit_exact(ref, got, ref_p, got_p, ref_s, got_s):
    for key in ("loss", "limit", "psi_bar", "accelerated", "sub_iters"):
        np.testing.assert_array_equal(ref[key], got[key], err_msg=key)
    for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(got_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(ref_s.accel_count) == int(got_s.accel_count)
    assert int(ref_s.sub_iters) == int(got_s.sub_iters)
    assert ref["accelerated"].sum() > 0, "subproblem never fired"


# ---------------------------------------------------------------------------
# bit-exact parity: single-device engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("K", [1, 4, 32])
def test_chunked_bit_exact_vs_per_step(K):
    loss_fn, params0, sampler, icfg = _problem(batch_size=8)
    rule = momentum(0.9)
    init_fn, step = make_train_step(loss_fn, rule, icfg, lr_fn=_lr_fn,
                                    donate=False)
    ref_s, ref_p, ref = _run_per_step(
        step, init_fn, params0,
        lambda j: {k: jnp.asarray(v) for k, v in sampler(j).items()}, STEPS)

    ring = DeviceRing(sampler.epoch_arrays(), sampler.batch_size)
    cinit, chunk = make_chunked_train_step(loss_fn, rule, icfg,
                                           chunk_steps=K, lr_fn=_lr_fn,
                                           donate=False)
    got_s, got_p, got = _run_chunked(chunk, cinit, params0, ring.arrays,
                                     STEPS, K)
    _assert_bit_exact(ref, got, ref_p, got_p, ref_s, got_s)


# ---------------------------------------------------------------------------
# bit-exact parity: shard_map engine (1 device under tier-1, 8 under CI)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("K", [1, 4, 32])
def test_chunked_data_parallel_bit_exact_vs_per_step(K):
    n_dev = len(jax.devices())
    loss_fn, params0, sampler, icfg = _problem(batch_size=8 * n_dev)
    rule = momentum(0.9)
    mesh = make_data_mesh()
    ring = DeviceRing(sampler.epoch_arrays(), sampler.batch_size, mesh=mesh)

    init_fn, step = make_data_parallel_step(loss_fn, rule, icfg, mesh,
                                            lr_fn=_lr_fn, donate=False)
    ref_s, ref_p, ref = _run_per_step(step, init_fn, params0, ring, STEPS)

    cinit, chunk = make_chunked_data_parallel_step(
        loss_fn, rule, icfg, mesh, chunk_steps=K, lr_fn=_lr_fn, donate=False)
    got_s, got_p, got = _run_chunked(chunk, cinit, params0, ring.arrays,
                                     STEPS, K)
    _assert_bit_exact(ref, got, ref_p, got_p, ref_s, got_s)


def test_chunked_consistent_step_runs():
    loss_fn, params0, sampler, icfg = _problem(batch_size=8)
    ring = DeviceRing(sampler.epoch_arrays(), sampler.batch_size)
    cinit, chunk = make_chunked_train_step(
        loss_fn, momentum(0.9), icfg, chunk_steps=4, inconsistent=False,
        lr_fn=_lr_fn, donate=False)
    s, p, ms = _run_chunked(chunk, cinit, params0, ring.arrays, 8, 4)
    assert not ms["accelerated"].any()
    assert np.isfinite(ms["loss"]).all()


def test_chunked_donation_across_chunks():
    """The production configuration: donated (state, params) carried chunk
    to chunk — donated inputs must not be reused by the caller."""
    loss_fn, params0, sampler, icfg = _problem(batch_size=8)
    ring = DeviceRing(sampler.epoch_arrays(), sampler.batch_size)
    cinit, chunk = make_chunked_train_step(loss_fn, momentum(0.9), icfg,
                                           chunk_steps=4, lr_fn=_lr_fn)
    p = jax.tree.map(jnp.copy, params0)
    s = cinit(p)
    for c in range(4):
        s, p, ms = chunk(s, p, ring.arrays, c * 4)
    assert np.isfinite(np.asarray(ms["loss"])).all()


# ---------------------------------------------------------------------------
# ring vs host sampler
# ---------------------------------------------------------------------------
def test_ring_matches_host_sampler_across_epochs():
    _, _, sampler, _ = _problem(batch_size=8, n_batches=3)
    ring = DeviceRing(sampler.epoch_arrays(), sampler.batch_size)
    assert (ring.n_batches, ring.batch_size) == (3, 8)
    for j in range(8):                      # wraps the cycle twice
        got, want = ring(j), sampler(j)
        assert ring.batch_index(j) == sampler.batch_index(j)
        for k in want:
            assert isinstance(got[k], jax.Array)
            np.testing.assert_array_equal(np.asarray(got[k]), want[k])


def test_sharded_ring_matches_host_sampler():
    mesh = make_data_mesh()
    n_dev = mesh.shape["data"]
    _, _, sampler, _ = _problem(batch_size=4 * n_dev, n_batches=3)
    ring = DeviceRing(sampler.epoch_arrays(), sampler.batch_size, mesh=mesh)
    assert ring.local_batch_size == 4
    for j in range(7):
        got, want = ring(j), sampler(j)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]), want[k])


def test_shard_layout_roundtrip():
    """Device d's contiguous block holds its slice of every batch in cycle
    order — the invariant the in-scan local dynamic_slice depends on."""
    n_b, n_dev, bsl = 3, 4, 2
    v = np.arange(n_b * n_dev * bsl * 5).reshape(n_b * n_dev * bsl, 5)
    out = _shard_layout(v, n_b, n_dev)
    bs = n_dev * bsl
    for d in range(n_dev):
        block = out[d * n_b * bsl:(d + 1) * n_b * bsl]
        for t in range(n_b):
            np.testing.assert_array_equal(
                block[t * bsl:(t + 1) * bsl],
                v[t * bs + d * bsl: t * bs + (d + 1) * bsl])


def test_ring_or_prefetch_fallback_and_promotion():
    _, _, sampler, _ = _problem(batch_size=8, n_batches=3)
    fb = ring_or_prefetch(sampler, byte_budget=16)       # epoch >> 16 bytes
    assert isinstance(fb, PrefetchSampler)
    ring = ring_or_prefetch(sampler, byte_budget=None)   # None = always fits
    assert isinstance(ring, DeviceRing)
    big = ring_or_prefetch(sampler,
                           byte_budget=sampler.epoch_nbytes())
    assert isinstance(big, DeviceRing)
    # the budget is per replica: a sharded ring only needs 1/n_dev per device
    mesh = make_data_mesh()
    n_dev = mesh.shape["data"]
    per_replica = -(-sampler.epoch_nbytes() // n_dev)
    assert isinstance(
        ring_or_prefetch(sampler, mesh=mesh, byte_budget=per_replica),
        DeviceRing)
    assert isinstance(
        ring_or_prefetch(sampler, mesh=mesh,
                         byte_budget=(sampler.epoch_nbytes() - n_dev) // n_dev),
        PrefetchSampler)
    for j in range(5):                     # both paths: identical batches
        want = sampler(j)
        for k in want:
            np.testing.assert_array_equal(np.asarray(fb(j)[k]), want[k])
            np.testing.assert_array_equal(np.asarray(ring(j)[k]), want[k])


# ---------------------------------------------------------------------------
# zero-copy sampler contract + TrainLog.extend
# ---------------------------------------------------------------------------
def test_fcpr_batches_are_contiguous_zero_copy_views():
    _, _, sampler, _ = _problem(batch_size=8)
    epoch = sampler.epoch_arrays()
    for v in epoch.values():
        assert v.flags["C_CONTIGUOUS"]
    b = sampler(1)
    for k, v in b.items():
        assert v.flags["C_CONTIGUOUS"]
        assert np.shares_memory(v, epoch[k])            # view, not copy
    assert sampler.epoch_nbytes() == sum(v.nbytes for v in epoch.values())


def test_explicit_batches_epoch_arrays():
    from repro.data import ExplicitBatches
    batches = [{"x": np.full((2, 3), i, np.float32)} for i in range(3)]
    eb = ExplicitBatches(batches)
    epoch = eb.epoch_arrays()
    assert epoch["x"].shape == (6, 3)
    ring = DeviceRing(epoch, eb.batch_size)
    for j in range(5):
        np.testing.assert_array_equal(np.asarray(ring(j)["x"]),
                                      eb(j)["x"])


def test_trainlog_extend_matches_append():
    loss_fn, params0, sampler, icfg = _problem(batch_size=8)
    init_fn, step = make_train_step(loss_fn, momentum(0.9), icfg,
                                    lr_fn=_lr_fn, donate=False)
    _, _, stacked = _run_per_step(
        step, init_fn, params0,
        lambda j: {k: jnp.asarray(v) for k, v in sampler(j).items()}, 8)

    ref = TrainLog()
    for i in range(8):
        ref.append({k: v[i] for k, v in stacked.items() if k != "aux"}, 0.5)
    got = TrainLog()
    got.extend(stacked, 0.5)
    assert got.losses == ref.losses
    assert got.limits == ref.limits
    assert got.psi_bar == ref.psi_bar
    assert got.accelerated == ref.accelerated
    assert got.sub_iters == ref.sub_iters
    assert got.wall == [0.5] * 8
    # chunk-end walls are estimates; per-step appends default to real walls
    assert got.wall_est == [True] * 8
    assert ref.wall_est == [False] * 8


def test_launcher_main_in_process_returns_chunk_record(monkeypatch, tmp_path):
    """``launch.train.main(argv)`` runs in-process and hands back what an
    in-process caller (``chip_smoke.py``) reads: the resolved kernel mode,
    one record per fused dispatch, and per-device memory stats."""
    from repro.launch.train import main
    # a set variable keeps main from turning on the in-checkout cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    res = main(["--model", "transformer", "--chunk-steps", "4", "--steps",
                "8", "--batch", "8", "--seq", "32", "--n-seqs", "32",
                "--devices", "1"])
    assert res.kernels == "reference" and res.steps == 8
    assert [c["step"] for c in res.chunks] == [4, 8]
    for c in res.chunks:
        assert c["wall_s"] > 0.0
        assert np.isfinite(c["metrics"]["loss"]).all()
        assert np.asarray(c["metrics"]["loss"]).shape == (4,)
    assert len(res.memory) == 1
    assert int(res.state.accel_count) >= 0
