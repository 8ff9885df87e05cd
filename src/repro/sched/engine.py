"""Device-resident scheduled step/chunk bodies.

``make_scheduled_body`` turns the un-jitted per-step ISGD body
(``train.trainer.make_step_core``) into a body that *selects* its batch on
device: draw ``t`` from the policy, fetch batch ``t`` as a
``dynamic_slice`` of the epoch ring arrays, run the step, feed the (already
globally-reduced) batch loss back to the policy.  Selection therefore
composes with :class:`~repro.data.device_ring.DeviceRing` and the fused
``lax.scan`` chunk engine with zero per-step host involvement — the host
dispatches once per chunk exactly as in ``repro.train.chunked``.

Determinism across data shards: the selection key is
``fold_in(PRNGKey(seed), step)`` — a pure function of the (replicated) step
index — and the loss driving ``update`` is the reduce-ctx-reduced ψ, so
under the manual shard_map strategy every shard derives the same key, sees
the same table, and draws the same index; under GSPMD there is only one
logical program.  The same argument that makes the accelerate ``cond``
branch identically on every device (core/reduce.py) covers the scheduler.

SPC coupling: for ``uses_table`` policies the step writes the loss queue at
slot ``t`` (``control.push_at``) instead of FIFO, so the control chart's
ψ̄/σ/limit read the per-batch loss table — see the ``repro.sched`` package
doc for why.  FCPR keeps the FIFO push, bit-exactly the pre-scheduler step.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.timing import CHUNK_SCAN, named_scope


def selection_counts(batch_idx, n_batches: int) -> np.ndarray:
    """Visit histogram over batches from a realized ``batch_idx`` sequence
    (a chunk's stacked metrics, or a whole run's) — the obs layer and the
    launch drivers share this one definition."""
    return np.bincount(np.asarray(batch_idx).ravel().astype(np.int64),
                       minlength=n_batches)


def make_scheduled_body(step_fn: Callable, schedule, n_batches: int,
                        seed: int = 0):
    """Wrap an un-jitted ``step_fn(state, params, batch, lr=None, slot=None)``
    into ``body(state, params, sched_state, ring_arrays, j) -> (state,
    params, sched_state, metrics)`` with on-device selection.

    ``ring_arrays`` is a dict of epoch arrays with ``n_batches *
    batch_size`` leading rows (a ``DeviceRing``'s ``.arrays``, or its local
    shard inside ``shard_map``); ``j`` is the global step index.  Metrics
    gain ``batch_idx`` — the selected batch, stacked per step by the chunk
    engine so drivers can log the realized visit sequence without extra
    fetches.
    """
    base_key = jax.random.PRNGKey(seed)

    def body(state, params, sched_state, ring_arrays, j):
        j = jnp.asarray(j, jnp.int32)
        key = jax.random.fold_in(base_key, j)
        t, sched_state = schedule.select(sched_state, j, key)
        bs = next(iter(ring_arrays.values())).shape[0] // n_batches
        batch = {k: jax.lax.dynamic_slice_in_dim(v, t * bs, bs)
                 for k, v in ring_arrays.items()}
        slot = t if schedule.uses_table else None
        state, params, metrics = step_fn(state, params, batch, slot=slot)
        sched_state = schedule.update(sched_state, t, metrics["loss"])
        metrics = dict(metrics, batch_idx=t)
        return state, params, sched_state, metrics

    return body


def chunk_over_schedule(step_fn: Callable, schedule, n_batches: int,
                        chunk_steps: int, seed: int = 0):
    """Scheduled twin of ``train.chunked.chunk_over_ring``: K policy-selected
    ISGD steps per dispatch.

    Returns ``chunk_fn(state, params, sched_state, ring_arrays, j0) ->
    (state, params, sched_state, stacked_metrics)`` — the schedule state
    rides the scan carry next to ``(state, params)``, so table updates from
    step ``j`` steer the selection at step ``j+1`` inside the same chunk.
    """
    assert chunk_steps >= 1
    body = make_scheduled_body(step_fn, schedule, n_batches, seed)

    def chunk_fn(state, params, sched_state, ring_arrays, j0):
        j0 = jnp.asarray(j0, jnp.int32)

        def scan_body(carry, off):
            state, params, sched_state = carry
            state, params, sched_state, metrics = body(
                state, params, sched_state, ring_arrays, j0 + off)
            return (state, params, sched_state), metrics

        with named_scope(CHUNK_SCAN):
            (state, params, sched_state), stacked = jax.lax.scan(
                scan_body, (state, params, sched_state),
                jnp.arange(chunk_steps, dtype=jnp.int32))
        return state, params, sched_state, stacked

    return chunk_fn
