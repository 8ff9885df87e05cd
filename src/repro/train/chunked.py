"""Fused multi-step training engine: K ISGD steps per host dispatch.

The per-step engine pays one jit dispatch, one host→device batch transfer
and (worst case) one host sync per iteration — at paper-reproduction scales
that fixed cost dominates the actual compute, the exact pipeline-throughput
trap Eq. 21's batch-size/cost model amortizes on the hardware side.  This
module amortizes it on the dispatch side: batches come from a device-
resident :class:`~repro.data.device_ring.DeviceRing` (FCPR makes batch
identity a pure function of the step index, so selection is a
``dynamic_slice``, no host involvement), and a ``lax.scan`` runs
``chunk_steps`` full ISGD iterations — queue push, control limit,
accelerate ``cond``, subproblem ``while_loop``, loss-driven LR — inside ONE
compiled dispatch, stacking the per-step metrics on device.  The host
fetches metrics once per chunk (``TrainLog.extend``) and ``(state, params)``
buffers are donated across chunks.

Semantics are bit-exact with the per-step engine because the scan body *is*
the per-step body (``trainer.make_step_core``): in particular the
loss-driven LR reads ψ̄ from the carry's queue *before* the step pushes its
own loss — the same one-step lag the host loop has, just carried on device.
Putting the ``lr_fn`` read anywhere else (e.g. after the push, or hoisted to
the chunk boundary) silently changes the schedule; see the parity test.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import ISGDConfig
from repro.core.reduce import LOCAL, ReduceCtx
from repro.obs.timing import CHUNK_SCAN, named_scope
from repro.optim.base import UpdateRule
from repro.train.trainer import make_step_core


def chunk_over_ring(step_fn: Callable, n_batches: int, chunk_steps: int):
    """Wrap an un-jitted ``step_fn(state, params, batch) -> (state, params,
    metrics)`` in a ``lax.scan`` over the FCPR ring.

    Returns ``chunk_fn(state, params, ring_arrays, j0) -> (state, params,
    stacked_metrics)`` where ``ring_arrays`` is a dict of epoch arrays with
    ``n_batches * batch_size`` leading rows (batch t at ``[t*bs, (t+1)*bs)``
    — a :class:`DeviceRing`'s ``.arrays``, or its local shard inside
    ``shard_map``) and ``j0`` is the global index of the chunk's first step.
    Stacked metrics have a (chunk_steps,) leading dim.
    """
    assert chunk_steps >= 1

    def chunk_fn(state, params, ring_arrays, j0):
        j0 = jnp.asarray(j0, jnp.int32)
        bs = next(iter(ring_arrays.values())).shape[0] // n_batches

        def body(carry, off):
            state, params = carry
            t = (j0 + off) % n_batches      # FCPR: batch identity from index
            batch = {k: jax.lax.dynamic_slice_in_dim(v, t * bs, bs)
                     for k, v in ring_arrays.items()}
            state, params, metrics = step_fn(state, params, batch)
            return (state, params), metrics

        with named_scope(CHUNK_SCAN):
            (state, params), stacked = jax.lax.scan(
                body, (state, params),
                jnp.arange(chunk_steps, dtype=jnp.int32))
        return state, params, stacked

    return chunk_fn


def make_chunked_train_step(loss_fn: Callable, rule: UpdateRule,
                            isgd_cfg: ISGDConfig, *, chunk_steps: int,
                            inconsistent: bool = True,
                            lr_fn: Callable = None, donate: bool = True,
                            reduce_ctx: ReduceCtx = LOCAL,
                            micro_batches: int = 1, schedule=None,
                            sched_seed: int = 0):
    """Single-device fused engine; distributed twin:
    ``repro.distributed.make_chunked_data_parallel_step``.

    Returns ``(init_fn, chunk_fn)`` with ``chunk_fn(state, params,
    ring_arrays, j0)`` jitted and donating ``(state, params)``.  ``lr_fn``
    is required — inside a fused chunk the LR *must* be derived on device
    from the previous step's queue; there is no host between steps to pass
    an override.

    ``schedule`` (a ``repro.sched`` policy) swaps the hard-wired FCPR ring
    walk for on-device policy selection: the chunk signature becomes
    ``chunk_fn(state, params, sched_state, ring_arrays, j0) -> (state,
    params, sched_state, stacked_metrics)`` with ``sched_state`` =
    ``schedule.init(isgd_cfg.n_batches)`` threaded through the scan carry
    (still one host dispatch per K steps; ``FCPRSchedule`` is bit-exact
    with ``schedule=None``).
    """
    assert lr_fn is not None, "chunked engine needs lr_fn (no per-step host)"
    init_fn, step_fn = make_step_core(
        loss_fn, rule, isgd_cfg, inconsistent=inconsistent, lr_fn=lr_fn,
        reduce_ctx=reduce_ctx, micro_batches=micro_batches)
    if schedule is not None:
        from repro.sched.engine import chunk_over_schedule
        chunk_fn = chunk_over_schedule(step_fn, schedule, isgd_cfg.n_batches,
                                       chunk_steps, sched_seed)
        jit_kwargs = dict(donate_argnums=(0, 1, 2)) if donate else {}
        return init_fn, jax.jit(chunk_fn, **jit_kwargs)
    chunk_fn = chunk_over_ring(step_fn, isgd_cfg.n_batches, chunk_steps)
    jit_kwargs = dict(donate_argnums=(0, 1)) if donate else {}
    return init_fn, jax.jit(chunk_fn, **jit_kwargs)
