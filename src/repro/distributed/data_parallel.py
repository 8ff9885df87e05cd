"""shard_map/GSPMD ISGD engine: pure data parallelism (paper §6, Fig. 8)
and the hybrid DP × TP regime on ``(data, model)`` / ``(pod, data, model)``
meshes — single-host or multi-process (see ``README.md`` in this package
for the full process-aware contract).

One engine, one step path.  ``make_hybrid_step`` runs the *same* step body
every other synchronous engine uses — ``train.trainer.make_step_core`` —
so the loss-driven LR (ψ̄ read from the queue with its one-step lag, Alg.1
line 19) is identical everywhere.  (Historical note: the old pjit runner
hand-rolled its own step closure and froze the schedule at ``lr_fn(0.0)``;
that closure is gone and tests/test_hybrid.py pins the fix.)  The engine
picks its execution strategy from the mesh through ONE dispatch point,
:func:`mesh_strategy`:

  * **manual shard_map over the data axes** — when every non-data axis is
    trivial (a 1-D ``('data',)`` mesh, ``(data, model=1)``, or
    ``(pod, data, model=1)``).  The batch is sharded over the data axes
    (leading dim); each device computes loss/gradients on its shard and
    ``AxisReduce`` reduces both, so the ``lax.cond`` accelerate predicate
    and every trip of the subproblem ``while_loop`` see replicated values —
    the invariant ``core/isgd.py`` documents.  Params and ISGD state are
    replicated.  The strategy always constructs
    ``AxisReduce(axes, deterministic=True)``: the gather-then-reduce mode
    whose f32 association is a pure function of the flat shard order, so a
    ``(pod=2, data=2)`` two-process mesh reproduces a single-process
    ``(data=4)`` mesh *bit-exactly* (``core/reduce.py``; pinned by
    ``repro.distributed.multihost_parity``).  This is the pure
    data-parallel regime the paper scales (its multi-GPU experiments
    replicate the model); ``make_data_parallel_step`` remains as the alias.

  * **GSPMD (pjit-with-constraints)** — when a model/tensor axis has size
    > 1.  The identical ``make_step_core`` body is jitted as a *global*
    program: params/velocity sharded over ``model`` by their placement
    (``launch/shardings.py``) plus any activation-sharding constraints,
    batch pinned to ``P(data)`` by an in-step ``with_sharding_constraint``.
    The reduction context stays ``LOCAL`` because the traced program
    already computes the *global*-batch loss/gradients — GSPMD partitions
    the batch dim over ``data`` and inserts the cross-device reductions
    itself, so ψ and the grads are the same real numbers the manual
    strategy reduces together (associated differently in f32; the hybrid
    parity suite bounds the difference and pins bit-exactness on the legs
    where the layouts coincide).

  Why two strategies instead of ``shard_map(..., auto={'model'})``: XLA's
  SPMD partitioner (jax 0.4.37) cannot partition ``lax.scan`` inside a
  manual subgroup (``Check failed: sharding.IsManualSubgroup()``), and
  scan is load-bearing everywhere here — the transformer block stack, the
  fused chunk engine, micro-batch accumulation.  The shardy partitioner
  lifts the limitation; :func:`mesh_strategy` is the ONLY place that knows
  the split exists, so deleting it when shardy becomes the default is a
  one-function change.

``make_hybrid_step`` mirrors ``train.trainer.make_train_step`` — same
``(init_fn, step_fn)`` contract, same metrics surface — so the host loop,
examples, and benchmarks can swap engines with one line.

Both factories accept ``schedule=`` (a ``repro.sched`` policy): batch
identity is then drawn on device inside the step/scan (selection key and
table updates replicated by construction, exactly like the accelerate
cond), the signatures gain a ``sched_state`` pytree, and batches come from
``DeviceRing`` epoch arrays instead of host transfers.  ``FCPRSchedule``
through this path is bit-exact with ``schedule=None``.

**Multi-process notes** — the factories are topology-agnostic; what makes
a multi-process run work is how the *inputs* are placed:

  * build the mesh with ``repro.launch.mesh.make_training_mesh`` (global
    devices, process-contiguous pod rows);
  * pass ``axis=None`` (or an explicit tuple like ``("pod", "data")``) so
    the strategy reduces over every data sub-axis;
  * feed batches from a :class:`~repro.data.device_ring.DeviceRing` (each
    process uploads only its epoch stripe) and replicate params/state with
    :func:`replicate_to_mesh` — a plain ``device_put`` cannot address
    other processes' devices.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import ISGDConfig
from repro.core.reduce import LOCAL, AxisReduce
from repro.optim.base import UpdateRule
from repro.train.chunked import chunk_over_ring
from repro.train.trainer import make_step_core


def _data_axes(axis) -> tuple:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def data_axis_size(mesh: Mesh, axis=None) -> int:
    """Total data-parallel degree: the product of the data axes' sizes
    (``axis=None`` = every pod/data axis of the mesh)."""
    if axis is None:
        from repro.launch.mesh import data_axes
        axis = data_axes(mesh)
    return int(np.prod([mesh.shape[a] for a in _data_axes(axis)]))


def batch_sharding(mesh: Mesh, axis=None) -> NamedSharding:
    """NamedSharding for host->device batch transfer (leading dim over the
    data axes — jointly, pod-major, when ``axis`` is a tuple or ``None``).

    Matches the step's data layout so the prefetcher's ``device_put`` lands
    shards exactly where the engine consumes them — no resharding copy.
    The batch is replicated over any model axis.
    """
    if axis is None:
        from repro.launch.mesh import data_axes
        axis = data_axes(mesh)
    axes = _data_axes(axis)
    return NamedSharding(mesh, P(axes[0] if len(axes) == 1 else axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def replicate_to_mesh(tree, mesh: Mesh):
    """Place a host-local pytree fully replicated on ``mesh`` — the
    multi-process-safe ``device_put``.

    On a single-process mesh this IS ``jax.device_put(x, P())``.  On a
    multi-process mesh ``device_put`` cannot address other processes'
    devices, so each leaf goes through
    ``jax.make_array_from_process_local_data`` instead: every process
    supplies its (identical — same seed, same init) host value and jax
    assembles the global replicated array.  Use this for params/ISGD
    state/sched state before handing them to the engines."""
    sh = replicated(mesh)
    procs = {d.process_index for d in mesh.devices.flat}
    if len(procs) <= 1:
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(sh, x, x.shape)

    return jax.tree.map(put, tree)


def tensor_axes(mesh: Mesh, axis=None) -> tuple:
    """Non-data mesh axes with size > 1 — the tensor/model-parallel part.

    Empty ⇒ the mesh is pure data parallel and the engine uses the manual
    shard_map strategy; non-empty ⇒ the GSPMD strategy (see module doc).
    """
    if axis is None:
        from repro.launch.mesh import data_axes
        axis = data_axes(mesh)
    data = set(_data_axes(axis))
    return tuple(a for a in mesh.axis_names
                 if a not in data and mesh.shape[a] > 1)


class MeshStrategy:
    """THE strategy dispatch point: everything the engines need to know
    about *how* a mesh executes, resolved once.

    ``reduce_ctx`` — what ``make_step_core`` reduces ψ/grads with;
    ``wrap_step``/``wrap_sched`` — how a traced body becomes a mesh
    program; ``constrain_batch`` — the GSPMD-side equivalent of the manual
    in_specs.  The manual/GSPMD split (see module doc: scan-in-manual-
    subgroup is its only reason to exist) lives entirely in this class —
    when shardy lands, collapse it here and no engine factory changes.
    """

    def __init__(self, mesh: Mesh, axis=None):
        if axis is None:
            from repro.launch.mesh import data_axes
            axes = data_axes(mesh)
            assert axes, f"mesh {tuple(mesh.shape)} has no data axes"
        else:
            axes = _data_axes(axis)
        self.mesh = mesh
        #: normalized data axis spec (str when single — preserves the
        #: historical P("data") spec objects and cache keys)
        self.axis = axes[0] if len(axes) == 1 else axes
        self.tensor_axes = tensor_axes(mesh, axes)
        #: True ⇒ GSPMD strategy (global program); False ⇒ manual shard_map
        self.gspmd = bool(self.tensor_axes)
        #: reduction context for ``make_step_core`` — LOCAL under GSPMD
        #: (the traced program spans the global batch); deterministic
        #: AxisReduce under manual, so the f32 association is pinned to
        #: the flat shard order and any process topology that preserves
        #: the data order reproduces the same bits.
        self.reduce_ctx = (LOCAL if self.gspmd
                           else AxisReduce(self.axis, deterministic=True))

    def wrap_step(self, fn: Callable) -> Callable:
        """4-ary step/chunk body (state, params, batch_or_ring, lr_or_j) ->
        mesh program.  Manual: shard_map with arg 2 sharded over the data
        axes.  GSPMD: the body already IS the global program."""
        if self.gspmd:
            return fn
        return shard_map(fn, mesh=self.mesh,
                         in_specs=(P(), P(), P(self.axis), P()),
                         out_specs=(P(), P(), P()),
                         check_vma=False)

    def wrap_sched(self, fn: Callable) -> Callable:
        """Scheduled twin of ``wrap_step`` for the 5-ary bodies from
        ``repro.sched.engine``: (state, params, sched_state, ring, j) with
        only the ring sharded.  The schedule state (loss table, visit
        counters) is replicated — its updates are driven by the reduced ψ
        and the step-index-derived key, so every shard writes the same
        values (the same replication-by-construction argument as the
        accelerate cond)."""
        if self.gspmd:
            return fn
        return shard_map(fn, mesh=self.mesh,
                         in_specs=(P(), P(), P(), P(self.axis), P()),
                         out_specs=(P(), P(), P(), P()),
                         check_vma=False)

    def constrain_batch(self, batch):
        """Pin every divisible batch leaf's leading dim to the data axes —
        the GSPMD strategy's equivalent of the manual in_specs; identity on
        the manual strategy (the shard_map specs already did it)."""
        if not self.gspmd:
            return batch
        size = data_axis_size(self.mesh, self.axis)
        sh = NamedSharding(self.mesh, P(self.axis))

        def leaf(x):
            if getattr(x, "ndim", 0) and x.shape[0] % size == 0:
                return jax.lax.with_sharding_constraint(x, sh)
            return x

        return jax.tree.map(leaf, batch)


def mesh_strategy(mesh: Mesh, axis=None) -> MeshStrategy:
    """Resolve the execution strategy for ``mesh`` (see
    :class:`MeshStrategy`).  ``axis=None`` spans every data sub-axis the
    mesh has (``("pod", "data")`` on a 3-D mesh)."""
    return MeshStrategy(mesh, axis)


def make_hybrid_step(loss_fn: Callable, rule: UpdateRule,
                     isgd_cfg: ISGDConfig, mesh: Mesh, *,
                     axis=None, inconsistent: bool = True,
                     lr_fn: Optional[Callable] = None,
                     micro_batches: int = 1, donate: bool = True,
                     schedule=None, sched_seed: int = 0):
    """Returns ``(init_fn, step_fn)`` with the ``make_train_step`` contract.

    ``step_fn(state, params, batch, lr=None) -> (state, params, metrics)``
    where ``batch`` leaves carry the *global* batch on their leading dim
    (divisible by the total data-axis size).  Params/state are replicated
    over the data axes; over any tensor-parallel axis their layout follows
    the caller's placement (``launch/shardings.py``).  All outputs are
    replicated over data: grads are globally reduced before the base
    update and ψ before the queue push, so every data shard computes the
    same new params.  When ``lr`` is not passed, ``lr_fn`` reads ψ̄ from
    the queue of the *incoming* state — the one-step lag of Alg.1 line 19,
    identical on both strategies because both run ``make_step_core``.

    ``axis=None`` resolves to the mesh's data sub-axes — ``("pod", "data")``
    on a process-aware 3-D mesh, ``"data"`` otherwise (the historical
    default).

    ``schedule`` (a ``repro.sched`` policy; requires ``lr_fn``) switches to
    on-device batch selection with the scheduled contract — ``step_fn(state,
    params, sched_state, ring_arrays, j) -> (state, params, sched_state,
    metrics)`` — where ``ring_arrays`` is a :class:`DeviceRing`'s
    ``.arrays`` (relaid-out on the manual strategy, ``relayout=False`` on
    GSPMD, exactly like the chunked engine).  Selection is replicated-
    deterministic across data shards *and processes*: the draw key is a
    pure function of the replicated step index, and the loss-table update
    consumes the ``AxisReduce``-reduced ψ.
    """
    if schedule is not None:
        return _make_scheduled_hybrid(
            loss_fn, rule, isgd_cfg, mesh, axis=axis,
            inconsistent=inconsistent, lr_fn=lr_fn,
            micro_batches=micro_batches, donate=donate, schedule=schedule,
            sched_seed=sched_seed, chunk_steps=None)
    strat = mesh_strategy(mesh, axis)
    jit_kwargs = dict(donate_argnums=(0, 1)) if donate else {}
    init_fn, core_step = make_step_core(
        loss_fn, rule, isgd_cfg, inconsistent=inconsistent, lr_fn=lr_fn,
        reduce_ctx=strat.reduce_ctx, micro_batches=micro_batches)

    if strat.gspmd:
        def step_fn(state, params, batch, lr=None):
            return core_step(state, params, strat.constrain_batch(batch), lr)

        return init_fn, jax.jit(step_fn, **jit_kwargs)

    sharded = strat.wrap_step(core_step)

    def step_fn(state, params, batch, lr=None):
        if lr is None:
            from repro.core import control as C
            lr = lr_fn(C.mean(state.queue))
        return sharded(state, params, batch, jnp.asarray(lr, jnp.float32))

    return init_fn, jax.jit(step_fn, **jit_kwargs)


def _make_scheduled_hybrid(loss_fn, rule, isgd_cfg, mesh, *, axis,
                           inconsistent, lr_fn, micro_batches, donate,
                           schedule, sched_seed, chunk_steps):
    """Shared scheduled-engine builder: per-step (``chunk_steps=None``) or
    fused chunk, on either mesh strategy.  Both return ``(init_fn, fn)``
    with ``fn(state, params, sched_state, ring_arrays, j_or_j0)`` and
    ``(state, params, sched_state)`` donated."""
    from repro.sched.engine import chunk_over_schedule, make_scheduled_body

    assert lr_fn is not None, "scheduled engine needs lr_fn (device-side LR)"
    strat = mesh_strategy(mesh, axis)
    init_fn, step_fn = make_step_core(
        loss_fn, rule, isgd_cfg, inconsistent=inconsistent, lr_fn=lr_fn,
        reduce_ctx=strat.reduce_ctx, micro_batches=micro_batches)
    if chunk_steps is None:
        body = make_scheduled_body(step_fn, schedule, isgd_cfg.n_batches,
                                   sched_seed)
    else:
        body = chunk_over_schedule(step_fn, schedule, isgd_cfg.n_batches,
                                   chunk_steps, sched_seed)
    inner = strat.wrap_sched(body)

    def fn(state, params, sched_state, ring_arrays, j):
        return inner(state, params, sched_state, ring_arrays,
                     jnp.asarray(j, jnp.int32))

    jit_kwargs = dict(donate_argnums=(0, 1, 2)) if donate else {}
    return init_fn, jax.jit(fn, **jit_kwargs)


def make_chunked_hybrid_step(loss_fn: Callable, rule: UpdateRule,
                             isgd_cfg: ISGDConfig, mesh: Mesh, *,
                             chunk_steps: int, axis=None,
                             inconsistent: bool = True,
                             lr_fn: Optional[Callable] = None,
                             micro_batches: int = 1, donate: bool = True,
                             schedule=None, sched_seed: int = 0):
    """Fused K-steps-per-dispatch twin of ``make_hybrid_step``.

    The ``lax.scan`` over ``repro.train.chunked.chunk_over_ring`` runs K
    full ISGD steps without the host in the loop; metrics come back stacked
    (chunk_steps,).  Strategy follows the mesh exactly as in the per-step
    engine:

      * manual shard_map — the scan runs per device; each data shard slices
        its own rows out of its local block of a *relaid-out* sharded
        :class:`DeviceRing` (``ring_arrays`` sharded over the data axes,
        layout documented in ``repro.data.device_ring``);
      * GSPMD — the scan is one global program; ``ring_arrays`` keep the
        *global* row order (``DeviceRing(relayout=False)``) and the in-scan
        ``dynamic_slice`` picks the global batch, which the partitioner
        re-lays-out per the step's constraints.

    Returns ``(init_fn, chunk_fn)``; ``chunk_fn(state, params, ring_arrays,
    j0) -> (state, params, stacked_metrics)`` with ``(state, params)``
    donated.

    ``schedule`` switches to the scheduled contract (``chunk_fn(state,
    params, sched_state, ring_arrays, j0)``) with on-device selection in
    the scan body — see ``make_hybrid_step``; still ONE host dispatch per
    K-step chunk, on both strategies.
    """
    assert lr_fn is not None, "chunked engine needs lr_fn (no per-step host)"
    if schedule is not None:
        return _make_scheduled_hybrid(
            loss_fn, rule, isgd_cfg, mesh, axis=axis,
            inconsistent=inconsistent, lr_fn=lr_fn,
            micro_batches=micro_batches, donate=donate, schedule=schedule,
            sched_seed=sched_seed, chunk_steps=chunk_steps)
    strat = mesh_strategy(mesh, axis)
    jit_kwargs = dict(donate_argnums=(0, 1)) if donate else {}
    init_fn, step_fn = make_step_core(
        loss_fn, rule, isgd_cfg, inconsistent=inconsistent, lr_fn=lr_fn,
        reduce_ctx=strat.reduce_ctx, micro_batches=micro_batches)
    chunk = chunk_over_ring(step_fn, isgd_cfg.n_batches, chunk_steps)
    wrapped = strat.wrap_step(chunk)

    def chunk_fn(state, params, ring_arrays, j0):
        return wrapped(state, params, ring_arrays,
                       jnp.asarray(j0, jnp.int32))

    return init_fn, jax.jit(chunk_fn, **jit_kwargs)


# The pure data-parallel engine IS the hybrid engine on a pure-data mesh
# (manual shard_map strategy); the historical names stay as aliases so
# callers that never go tensor-parallel keep reading naturally.
make_data_parallel_step = make_hybrid_step
make_chunked_data_parallel_step = make_chunked_hybrid_step
