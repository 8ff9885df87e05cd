"""Distributed training launcher.

On real TPU hardware this runs the ISGD train loop under the production
mesh; on this CPU container it runs reduced configs under a host mesh so the
whole path (sharded params, sharded ISGD step with its cond/while_loop,
loss-driven LR) is exercised end-to-end.

Every synchronous engine builds its step through ONE path —
``train.trainer.make_step_core`` wrapped by the hybrid shard_map engine in
``repro.distributed.data_parallel`` — so the loss-driven LR (ψ̄ read with
its one-step lag, Alg.1 line 19) is identical everywhere.  (Historical
note: the old pjit runner hand-rolled its own step closure and froze the
schedule at ``lr_fn(0.0)``; that closure is gone and tests/test_hybrid.py
pins the fix.)  Engines (``--engine``; ``--data-parallel`` remains as an
alias):

  * ``hybrid`` (default; ``pjit`` is an alias) — the DP × TP engine on a
    2-D ``(data, model)`` host mesh: batch sharded over 'data' with
    grads/ψ globally reduced there, params/velocity sharded over 'model'
    (launch/shardings.py, ``--model-parallel M``) with activation
    constraints.  With ``M=1`` the engine runs the manual shard_map
    strategy (explicit AxisReduce pmeans — identical to data-parallel);
    with ``M>1`` the same step body runs as one GSPMD program
    (pjit-with-constraints) — see repro.distributed.data_parallel for why;
  * ``data-parallel`` — the same engine on a 1-D ('data',) mesh: params
    and ISGD state replicated, batch sharded over 'data' (paper §6);
  * ``async-ps`` — the asynchronous parameter-server engine (paper §6.2,
    repro.distributed.async_ps): ``--workers`` threads over per-worker FCPR
    shards push staleness-weighted deltas (``--staleness-decay``, w(τ)) to
    a server that runs the SPC limit/accelerate logic on globally
    consistent statistics; ``--max-staleness`` bounds how far workers may
    drift apart (0 = lockstep rounds — the synchronous schedule).

Two input/dispatch accelerators compose with the synchronous engines
(async-ps is host-orchestrated per worker step and rejects them):

  * ``--device-ring`` — serve batches from the device-resident FCPR ring
    (one epoch upload, batches by dynamic_slice) instead of per-step host
    transfers; falls back to the prefetcher when the epoch busts the byte
    budget;
  * ``--chunk-steps K`` — the fused engine: K full ISGD steps per host
    dispatch (lax.scan over the ring, bit-exact with per-step; the step
    count is rounded up to whole chunks);
  * ``--schedule fcpr|loss-prop|rank`` — batch *selection* policy
    (``repro.sched``): selection runs inside the jitted step over the
    device ring (implied), so loss-aware policies never round-trip their
    table through the host.  ``fcpr`` through the scheduler path is
    bit-exact with the default engines; under ``loss-prop``/``rank`` the
    SPC chart reads the per-batch loss table (ψ-window caveat — see the
    ``repro.sched`` package doc).  Omitting the flag keeps the hard-wired
    FCPR paths.

Fault tolerance (ISSUE 7): ``--checkpoint-dir``/``--checkpoint-every``
write crash-consistent full-engine checkpoints (atomic, checksummed .npz
covering params, optimizer base, ψ queue, sched state, step cursor, and —
async-ps — the server version + per-worker SSP push clocks); ``--resume``
restores the newest one and continues the uninterrupted trajectory
bit-exactly (``repro.train.resume_parity`` proves it per engine).  The
async-ps engine additionally takes ``--elastic`` (evict deadline-missing/
crashed workers, re-stripe their FCPR shard across survivors),
``--deadline``, ``--fault-plan`` (deterministic fault injection,
``repro.fault``) and ``--verify-pushes`` (checksum-reject corrupt deltas,
bounded retry).

Model selection: ``--arch`` names an assigned architecture config
(``repro.configs``, usually with ``--reduced``); ``--model
transformer|moe|ssm`` picks the ``paper_transformer`` zoo family instead
(``--tier tiny|base``).  ``--kernels pallas|reference|interpret`` routes the
step-body hot spots (flash-attention, fused-xent, ssd_scan) —
``pallas`` needs a TPU backend and exits with an error elsewhere (see
``repro.kernels.policy``); ``--precision bf16|f32`` is the
compute dtype (ψ statistics and the SPC queue stay f32 either way);
``--remat full|tp_out|none`` sets the chunk-scan-boundary checkpoint policy.
``--devices N`` trains on the first N devices of a single process.

``main(argv)`` can be called in-process and returns a :class:`TrainResult`
(``chip_smoke.py`` drives it that way).  JAX's persistent compilation cache
is on (``repro.launch.env.setup_compilation_cache``).

Multi-process (ROADMAP: multi-host 3-D mesh scale-out): every runner
accepts the shared ``--coordinator/--num-processes/--process-id`` surface
(``repro.launch.env``).  When present, ``jax.distributed.initialize`` is
wired up before any device use, the mesh factory produces a
``(pod, data, model)`` mesh over the *global* device set (one pod row per
process), ψ/grads reduce over ``("pod", "data")`` deterministically, the
FCPR epoch is striped per process through the :class:`DeviceRing` (each
process uploads only its rows), and checkpoints follow process-0-writes /
all-validate (``repro.train.checkpoints``).  A 2-process ``(2, 2, 1)`` run
is bit-exact with the single-process ``(4, 1)`` run
(``repro.distributed.multihost_parity``).  Library validation errors
(:class:`repro.launch.mesh.MeshError`) are translated to ``SystemExit``
here, at the CLI boundary — library code never exits.

  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --reduced --steps 30 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --model transformer \
      --kernels interpret --chunk-steps 32 --steps 64 --batch 8 --seq 64
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --arch internlm2-1.8b --reduced \
      --engine hybrid --model-parallel 2 --chunk-steps 8 --steps 32 \
      --batch 16
  # two cooperating processes on one machine (2 CPU devices each):
  XLA_FLAGS=--xla_force_host_platform_device_count=2 PYTHONPATH=src \
      python -m repro.launch.train --model transformer --steps 16 \
      --batch 8 --coordinator 127.0.0.1:9911 --num-processes 2 \
      --process-id 0   # and the same command with --process-id 1
"""
from __future__ import annotations

import argparse
import contextlib
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

import numpy as np

from repro.configs import ZOO_MODELS, ZOO_TIERS, get_config, zoo_config
from repro.core import ISGDConfig
from repro.core.schedule import constant_lr
from repro.data import DeviceRing, FCPRSampler, make_lm_tokens, ring_or_prefetch
from repro.distributed import (PrefetchSampler, batch_sharding,
                               make_chunked_hybrid_step, make_hybrid_step,
                               tensor_axes)
from repro.distributed.data_parallel import replicate_to_mesh
from repro.launch import env as ENV
from repro.launch import shardings as SH
from repro.launch.mesh import (MeshError, is_multiprocess, make_data_mesh,
                               make_training_mesh)
from repro.models import build_model
from repro.obs import timing as OT
from repro.obs.timing import StepTimer, maybe_profile
from repro.optim import RULES
from repro.sharding import activation_sharding, rules


def frontend_embeds(cfg, batch_size: int):
    """Constant zero frontend embeddings for vlm/encdec smoke configs —
    hoisted out of the step loop (they never change across steps)."""
    if cfg.family == "vlm":
        shape = (batch_size, cfg.num_image_tokens, cfg.d_model)
    elif cfg.family == "encdec":
        shape = (batch_size, cfg.encoder_seq, cfg.d_model)
    else:
        return {}
    return {"frontend_embeds": jnp.zeros(shape, jnp.bfloat16)}


def ring_epoch(cfg, sampler, batch_size: int):
    """Epoch arrays for a ``DeviceRing``, with the constant frontend extras
    tiled per-sample so an in-scan ring slice reproduces exactly the batch
    dict the per-step loop would have assembled."""
    epoch = dict(sampler.epoch_arrays())
    for k, v in frontend_embeds(cfg, batch_size).items():
        arr = np.asarray(v)
        epoch[k] = np.tile(arr, (sampler.n_batches,) + (1,) * (arr.ndim - 1))
    return epoch


@dataclass
class TrainResult:
    """What :func:`main` returns to an in-process caller."""

    state: object             # final ISGDState (accel_count, sub_iters, queue)
    seconds: float            # wall of the training loop, compiles included
    steps: int                # steps this invocation ran
    n_params: int = 0
    kernels: str = "reference"
    #: fused engine: one entry per dispatch — ``step`` (global step after
    #: it), ``wall_s`` (dispatch to fetched metrics; the first includes
    #: the compile) and ``metrics`` (host copies of the stacked metrics)
    chunks: list = field(default_factory=list)
    #: ``memory_stats()`` of each mesh device right after the loop, while
    #: params, state and the ring are live (None where a backend has none)
    memory: list = field(default_factory=list)


def _drive_chunks(jchunk, state, params, ring, steps: int, k: int, *,
                  start: int = 0, ckpt=None, obs=None):
    """Run from global step ``start`` to ``steps`` (rounded up to whole
    chunks) through a fused chunk fn, printing the last step of each chunk.
    ``start`` may sit mid-chunk relative to the K grid — ``chunk_fn`` takes
    an arbitrary ``j0`` (what makes resume-from-checkpoint possible).
    Returns (state, total_steps, chunks) with ``chunks`` as in
    :class:`TrainResult`.  ``obs`` ingests each chunk's stacked metrics at
    the chunk boundary (the fetch below is already the one host sync per
    chunk — obs adds no dispatches)."""
    j = start
    chunks = []
    while j < steps:
        t0 = time.perf_counter()
        with OT.annotate(OT.TRAIN_DISPATCH):
            state, params, ms = jchunk(state, params, ring.arrays, j)
        with OT.annotate(OT.TRAIN_FETCH):
            ms = jax.device_get(ms)
        wall = time.perf_counter() - t0
        if obs is not None:
            with OT.annotate(OT.TRAIN_OBS):
                obs.chunk(j, ms)
        j += k
        chunks.append({"step": j, "wall_s": wall, "metrics": ms})
        with OT.annotate(OT.TRAIN_LOG):
            ENV.p0print(f"step {j:4d} loss={float(ms['loss'][-1]):.4f} "
                        f"psi_bar={float(ms['psi_bar'][-1]):.4f} "
                        f"limit={float(ms['limit'][-1]):.4f} "
                        f"accel={bool(ms['accelerated'][-1])}")
        if ckpt is not None:
            with OT.annotate(OT.TRAIN_CHECKPOINT):
                ckpt.maybe_save(j, params=params, state=state)
    return state, j, chunks


def _drive_scheduled(jfn, state, params, sched_state, ring, steps: int,
                     k: int, *, start: int = 0, ckpt=None, obs=None):
    """Drive a scheduled engine (per-step when ``k == 1``, fused chunks
    otherwise), printing the last step of each dispatch group including the
    policy's realized batch pick.  Returns (state, total_steps)."""
    from repro.sched.engine import selection_counts
    if k == 1:
        for j in range(start, steps):
            state, params, sched_state, m = jfn(state, params, sched_state,
                                                ring.arrays, j)
            if obs is not None:
                obs.defer(j, m)
            if (j + 1) % 5 == 0 or j == 0:
                if obs is not None:
                    obs.flush()
                ENV.p0print(f"step {j+1:4d} batch={int(m['batch_idx'])} "
                      f"loss={float(m['loss']):.4f} "
                      f"psi_bar={float(m['psi_bar']):.4f} "
                      f"limit={float(m['limit']):.4f} "
                      f"accel={bool(m['accelerated'])}")
            if ckpt is not None:
                ckpt.maybe_save(j + 1, params=params, state=state,
                                sched_state=sched_state)
        if obs is not None:
            obs.flush()
        return state, steps
    j = start
    while j < steps:
        with OT.annotate(OT.TRAIN_DISPATCH):
            state, params, sched_state, ms = jfn(state, params, sched_state,
                                                 ring.arrays, j)
        with OT.annotate(OT.TRAIN_FETCH):
            ms = jax.device_get(ms)
        if obs is not None:
            with OT.annotate(OT.TRAIN_OBS):
                obs.chunk(j, ms)
        j += k
        with OT.annotate(OT.TRAIN_LOG):
            visits = selection_counts(ms["batch_idx"], ring.n_batches)
            ENV.p0print(f"step {j:4d} loss={float(ms['loss'][-1]):.4f} "
                        f"psi_bar={float(ms['psi_bar'][-1]):.4f} "
                        f"limit={float(ms['limit'][-1]):.4f} "
                        f"accel={bool(ms['accelerated'][-1])} "
                        f"visits={visits.tolist()}")
        if ckpt is not None:
            with OT.annotate(OT.TRAIN_CHECKPOINT):
                ckpt.maybe_save(j, params=params, state=state,
                                sched_state=sched_state)
    return state, j


class _TeeCheckpointer:
    """Fan a run's saves out to several ``Checkpointer``s — the
    crash-recovery directory and the serving publish directory can differ
    (different cadences, different pruning) without threading two objects
    through every runner."""

    def __init__(self, ckpts):
        self.ckpts = ckpts
        self.directory = ckpts[0].directory

    def maybe_save(self, step, **kw):
        outs = [c.maybe_save(step, **kw) for c in self.ckpts]
        return next((o for o in outs if o), None)

    def save(self, step, **kw):
        return [c.save(step, **kw) for c in self.ckpts][0]

    def mark(self, step):
        for c in self.ckpts:
            c.mark(step)

    def latest(self):
        return self.ckpts[0].latest()


def _make_checkpointer(args, recorder=None):
    """``--checkpoint-dir``/``--checkpoint-every`` → a ``Checkpointer``;
    ``--publish-dir`` adds (or upgrades to) a *publishing* checkpointer
    that maintains the atomic ``LATEST`` pointer a serving
    ``SnapshotWatcher`` polls (train-and-serve).  None when both are off."""
    import os

    from repro.train.checkpoints import Checkpointer
    publish_dir = args.publish_dir
    same = (publish_dir and args.checkpoint_dir and
            os.path.abspath(publish_dir) == os.path.abspath(args.checkpoint_dir))
    ckpts = []
    if args.checkpoint_dir:
        ckpts.append(Checkpointer(args.checkpoint_dir,
                                  every=args.checkpoint_every,
                                  pointer=bool(same), recorder=recorder))
    if publish_dir and not same:
        every = args.publish_every or args.checkpoint_every
        if not every:
            raise SystemExit("--publish-dir needs --publish-every (or "
                             "--checkpoint-every) to set the snapshot "
                             "cadence")
        ckpts.append(Checkpointer(publish_dir, every=every, pointer=True,
                                  recorder=recorder))
    if not ckpts:
        if args.resume:
            raise SystemExit("--resume needs --checkpoint-dir")
        return None
    return ckpts[0] if len(ckpts) == 1 else _TeeCheckpointer(ckpts)


def _maybe_resume(args, ckpt, *, params_like, state_like, sched_like=None):
    """``--resume``: restore the newest complete checkpoint in the directory
    (atomic saves guarantee completeness) against the freshly initialized
    templates.  Returns the ``EngineCheckpoint`` or None."""
    if not (args.resume and ckpt is not None):
        return None
    from repro.train.checkpoints import restore_engine
    latest = ckpt.latest()
    if latest is None:
        ENV.p0print(f"resume: no checkpoint under {ckpt.directory!r}; "
                    f"starting fresh")
        return None
    ck = restore_engine(latest, params_like=params_like,
                        state_like=state_like, sched_like=sched_like)
    ckpt.mark(ck.step)
    ENV.p0print(f"resume: restored {latest!r} at step {ck.step}")
    return ck


def _make_observer(args, cfg, icfg, engine: str):
    """``--obs-dir`` → a ``TrainObserver`` writing this process's JSONL
    (tagged process_id/engine/model), or None when obs is off.

    The SPC exporter mirrors the queue discipline of the selected engine:
    per-batch table replay for ``uses_table`` schedules, FIFO otherwise.
    Multi-worker async-PS runs push in commit order but observe losses in a
    (possibly different) race order, so their table replay is chart-only —
    counters still reconcile exactly (``replay_exact=False``)."""
    if not args.obs_dir:
        return None
    import os

    from repro.obs import (ConsoleSink, JsonlSink, MetricsRecorder,
                           TrainObserver, jsonl_path)
    topo = ENV.topology()
    os.makedirs(args.obs_dir, exist_ok=True)
    sinks = [JsonlSink(jsonl_path(args.obs_dir, topo.process_id))]
    if args.obs_console_every:
        sinks.append(ConsoleSink(every=args.obs_console_every))
    rec = MetricsRecorder(sinks, tags={"process_id": topo.process_id,
                                       "engine": engine, "model": cfg.name})
    table = False
    if args.schedule is not None and engine != "async-ps":
        from repro.sched import schedule_from_spec
        table = schedule_from_spec(args.schedule).uses_table
    replay_exact = engine != "async-ps" or args.workers == 1
    return TrainObserver(rec, n_batches=icfg.n_batches,
                         k_sigma=icfg.k_sigma, table=table,
                         examples_per_step=args.batch,
                         replay_exact=replay_exact)


def run_sync(args, cfg, model, sampler, rule, icfg, lr_fn, *,
             engine: str = "hybrid", obs=None):
    """The synchronous engines — ``hybrid`` (DP × TP, 2-D mesh) and
    ``data-parallel`` (1-D mesh) — one driving loop, one step path
    (``make_step_core`` under the hybrid shard_map engine).  Returns a
    :class:`TrainResult`.  ``obs`` (a ``repro.obs.TrainObserver``) ingests
    metrics at the existing chunk/log boundaries only."""
    timer = obs.timer if obs is not None else StepTimer()
    devices = None
    if args.devices:
        if ENV.topology().num_processes > 1:
            raise SystemExit("--devices picks devices of a single process; "
                             "multi-process runs use every global device")
        if args.devices > len(jax.devices()):
            raise SystemExit(f"--devices {args.devices}: this process has "
                             f"{len(jax.devices())}")
        devices = jax.devices()[:args.devices]
    if engine == "data-parallel":
        if args.model_parallel != 1:
            raise SystemExit("--model-parallel composes with --engine "
                             "hybrid, not --engine data-parallel")
        mesh = make_data_mesh(devices)
    else:
        # pod defaults to the process count: 2-D (data, model) single-
        # process, 3-D (pod, data, model) over global devices otherwise
        mesh = make_training_mesh(model=args.model_parallel, devices=devices)
    multiproc = is_multiprocess(mesh)
    from repro.distributed.data_parallel import data_axis_size
    n_data = data_axis_size(mesh)
    if args.batch % n_data:
        raise SystemExit(f"--batch {args.batch} must be a multiple of the "
                         f"{n_data} data-axis devices (it is split across "
                         f"them)")
    ENV.p0print(f"arch={cfg.name} engine={engine} mesh={dict(mesh.shape)} "
                f"processes={ENV.topology().num_processes} "
                f"per_device_batch={args.batch // n_data} "
                f"chunk_steps={args.chunk_steps}")

    params = model.init(jax.random.PRNGKey(0), max_seq=args.seq)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    tp = bool(tensor_axes(mesh))
    if multiproc and tp:
        raise SystemExit("--model-parallel > 1 is not wired for "
                         "multi-process runs yet (tensor-sharded param "
                         "placement needs per-process shard assembly); run "
                         "model parallelism single-process or data "
                         "parallelism multi-process")
    if multiproc:
        # every process initialized identical params (same PRNGKey):
        # assemble them into one replicated global array per leaf
        params = replicate_to_mesh(params, mesh)
        from repro.distributed.data_parallel import replicated
        p_sh = jax.tree.map(lambda _: replicated(mesh), params)
    else:
        params, p_sh = SH.hybrid_params_placement(mesh, params)
    if tp:
        # GSPMD strategy: tensor/FSDP-parallel weights + the activation
        # constraint table (valid here — the step is one global program)
        table = rules.activation_rule_table(mesh, args.batch)
        ctx = activation_sharding(rules.make_constrain(mesh, table))
        ENV.p0print(f"params: {n_params/1e6:.1f}M (model/FSDP-sharded)")
    else:
        # manual shard_map strategy: params replicated; constraints would
        # be illegal inside the manual region and are not needed
        ctx = contextlib.nullcontext()
        ENV.p0print(f"params: {n_params/1e6:.1f}M (replicated)")

    schedule = None
    if args.schedule is not None:
        from repro.sched import schedule_from_spec
        schedule = schedule_from_spec(args.schedule)
        ENV.p0print(f"schedule: {schedule} (device-resident selection; "
                    f"non-FCPR policies read SPC limits from the per-batch "
                    f"loss table)")
    if args.chunk_steps > 1:
        init_fn, jstep = make_chunked_hybrid_step(
            model.loss_fn, rule, icfg, mesh, chunk_steps=args.chunk_steps,
            inconsistent=not args.consistent, lr_fn=lr_fn,
            schedule=schedule)
    else:
        init_fn, jstep = make_hybrid_step(
            model.loss_fn, rule, icfg, mesh,
            inconsistent=not args.consistent, lr_fn=lr_fn,
            schedule=schedule)
    state = init_fn(params)
    s_sh = SH.state_shardings(mesh, jax.eval_shape(lambda: state), p_sh)
    ckpt = _make_checkpointer(args,
                              recorder=obs.recorder if obs is not None else None)
    start = 0

    def result(state, steps, chunks=()):
        # called inside the mesh context, while params/state/ring are live
        return TrainResult(state, timer.seconds("train"), steps - start,
                           n_params=n_params, chunks=list(chunks),
                           memory=[d.memory_stats() for d in mesh.devices.flat])

    put_repl = ((lambda t, _sh: replicate_to_mesh(t, mesh)) if multiproc
                else jax.device_put)
    with mesh, ctx:
        state = put_repl(state, s_sh)
        if schedule is not None:
            # scheduled engines select on device: the ring is mandatory
            ring = DeviceRing(ring_epoch(cfg, sampler, args.batch),
                              args.batch, mesh=mesh, axis=None,
                              relayout=not tp)
            sched_state = schedule.init(icfg.n_batches)
            ck = _maybe_resume(args, ckpt, params_like=params,
                               state_like=state, sched_like=sched_state)
            if ck is not None:
                params = put_repl(ck.params, p_sh)
                state = put_repl(ck.state, s_sh)
                sched_state, start = ck.sched_state, ck.step
            with timer.span("train"):
                state, steps = _drive_scheduled(jstep, state, params,
                                                sched_state, ring, args.steps,
                                                args.chunk_steps, start=start,
                                                ckpt=ckpt, obs=obs)
            return result(state, steps)
        ck = _maybe_resume(args, ckpt, params_like=params, state_like=state)
        if ck is not None:
            params = put_repl(ck.params, p_sh)
            state = put_repl(ck.state, s_sh)
            start = ck.step
        if args.chunk_steps > 1:
            # fused engine: sharded device ring + K steps per dispatch
            # (manual strategy slices its relaid-out local block; GSPMD
            # strategy slices the global row order)
            ring = DeviceRing(ring_epoch(cfg, sampler, args.batch),
                              args.batch, mesh=mesh, axis=None,
                              relayout=not tp)
            with timer.span("train"):
                state, steps, chunks = _drive_chunks(
                    jstep, state, params, ring, args.steps, args.chunk_steps,
                    start=start, ckpt=ckpt, obs=obs)
            return result(state, steps, chunks)

        if multiproc:
            # the host prefetcher's device_put cannot address other
            # processes' devices: the striped device ring is the only
            # multi-process feed (each process uploads its epoch stripe;
            # frontend extras are tiled into the ring)
            feed = DeviceRing(ring_epoch(cfg, sampler, args.batch),
                              args.batch, mesh=mesh, axis=None,
                              relayout=not tp)
            extra = {}
            ENV.p0print("input: DeviceRing (per-process epoch striping)")
        else:
            b_sh = batch_sharding(mesh)
            extra = {k: jax.device_put(v, b_sh)
                     for k, v in frontend_embeds(cfg, args.batch).items()}
            if args.device_ring:
                feed = ring_or_prefetch(sampler, mesh=mesh, axis=None,
                                        relayout=not tp)  # ring if it fits
                print(f"input: {type(feed).__name__}")
            else:
                feed = PrefetchSampler(
                    sampler,
                    sharding=SH.data_parallel_shardings(mesh, sampler(0)))
        with timer.span("train"):
            for j in range(start, args.steps):
                batch = dict(feed(j), **extra)
                state, params, m = jstep(state, params, batch)
                if obs is not None:
                    obs.defer(j, m)
                if (j + 1) % 5 == 0 or j == 0:
                    # the print below host-syncs anyway: flush obs here too
                    if obs is not None:
                        obs.flush()
                    ENV.p0print(f"step {j+1:4d} loss={float(m['loss']):.4f} "
                          f"psi_bar={float(m['psi_bar']):.4f} "
                          f"limit={float(m['limit']):.4f} "
                          f"accel={bool(m['accelerated'])}")
                if ckpt is not None:
                    ckpt.maybe_save(j + 1, params=params, state=state)
            if obs is not None:
                obs.flush()
        return result(state, args.steps)


def run_async_ps(args, cfg, model, sampler, rule, icfg, lr_fn, *, obs=None):
    from repro.distributed import AsyncPSCoordinator, staleness_reduce_from_spec
    from repro.distributed.async_ps.coordinator import (
        snapshot_engine_kwargs, snapshot_from_checkpoint)

    if cfg.family in ("vlm", "encdec"):
        raise SystemExit("--engine async-ps supports decoder-only/cnn "
                         "configs (no constant frontend-embed plumbing)")
    if args.chunk_steps > 1 or args.device_ring:
        raise SystemExit("--chunk-steps/--device-ring do not compose with "
                         "--engine async-ps (workers dispatch per step from "
                         "host snapshots, there is no fused scan or device "
                         "ring in this engine)")
    if args.schedule is not None:
        raise SystemExit("--schedule does not compose with --engine "
                         "async-ps (workers own fixed FCPR stripes; a "
                         "shared selection policy would race the table)")
    if sampler.n_batches % args.workers:
        # legal since re-striping (ISSUE 7): the strided shards still cover
        # the global cycle, ownership just rotates (see ShardedFeed)
        print(f"note: n_batches={sampler.n_batches} not a multiple of "
              f"--workers {args.workers}; per-worker batch ownership "
              f"rotates through the FCPR cycle")
    faults = None
    if args.fault_plan:
        from repro.fault import FaultPlan
        faults = FaultPlan.from_spec(args.fault_plan)
        print(f"faults: {faults}")
    rctx = staleness_reduce_from_spec(args.staleness_decay)
    print(f"arch={cfg.name} engine=async-ps workers={args.workers} "
          f"max_staleness={args.max_staleness} w(tau)={args.staleness_decay} "
          f"elastic={args.elastic} deadline={args.deadline:.0f}s")

    params = model.init(jax.random.PRNGKey(0), max_seq=args.seq)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.1f}M (canonical copy on the server)")

    kw = dict(elastic=args.elastic, deadline_s=args.deadline,
              verify_pushes=args.verify_pushes)
    if faults is not None:
        kw["faults"] = faults
    coord = AsyncPSCoordinator(
        model.loss_fn, rule, icfg, workers=args.workers,
        max_staleness=args.max_staleness, lr_fn=lr_fn, reduce_ctx=rctx,
        inconsistent=not args.consistent,
        recorder=obs.recorder if obs is not None else None, **kw)

    ckpt = _make_checkpointer(args,
                              recorder=obs.recorder if obs is not None else None)
    resume = None
    if args.resume and ckpt is not None and ckpt.latest() is not None:
        from repro.core import isgd_init
        from repro.train.checkpoints import restore_engine
        ck = restore_engine(ckpt.latest(), params_like=params,
                            state_like=isgd_init(rule, icfg, params))
        ckpt.mark(ck.step)
        resume = snapshot_from_checkpoint(ck)
        print(f"resume: restored {ckpt.latest()!r} at server version "
              f"{ck.server['version']} (worker push clocks: "
              f"{ck.server['pushed']})")

    def checkpoint_fn(snap):
        ek = snapshot_engine_kwargs(snap)
        ckpt.save(ek.pop("step"), **ek)

    run_kw = {}
    if ckpt is not None and args.checkpoint_every:
        run_kw = dict(checkpoint_fn=checkpoint_fn,
                      checkpoint_every=args.checkpoint_every)
    timer = obs.timer if obs is not None else StepTimer()
    with timer.span("train"):
        params, state, records = coord.run(params, sampler, args.steps,
                                           resume=resume, **run_kw)
    dt = timer.seconds("train")
    if obs is not None:
        obs.async_run(records, coord.events)
    for ev in coord.events:
        print(f"event: {ev}")
    for i, r in enumerate(records):
        if (i + 1) % 5 == 0 or i == 0:
            print(f"push {i+1:4d} w{r['worker']} tau={r['tau']} "
                  f"loss={r['loss']:.4f} psi_bar={r['psi_bar']:.4f} "
                  f"limit={r['limit']:.4f} accel={r['accelerated']}")
    taus = [r["tau"] for r in records]
    print(f"staleness: mean_tau={sum(taus)/len(taus):.2f} "
          f"max_tau={max(taus)} "
          f"bound={(2 * args.max_staleness + 1) * (args.workers - 1)}")
    return TrainResult(state, dt, len(records), n_params=n_params)


def main(argv=None) -> TrainResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="assigned architecture config (repro.configs)")
    ap.add_argument("--model", default=None, choices=list(ZOO_MODELS),
                    help="paper_transformer zoo family (alternative to "
                         "--arch): transformer | moe | ssm")
    ap.add_argument("--tier", default="tiny", choices=list(ZOO_TIERS),
                    help="zoo tier for --model (tiny = CPU CI, base = "
                         "single-host accelerator)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant (CPU)")
    ap.add_argument("--kernels", default="reference",
                    choices=["pallas", "reference", "interpret"],
                    help="step-body hot-spot implementations: pallas = "
                         "Mosaic kernels (TPU only), interpret = the same "
                         "kernels through the Pallas interpreter "
                         "(repro.kernels.policy)")
    ap.add_argument("--precision", default="bf16", choices=["bf16", "f32"],
                    help="compute dtype for params/activations (psi "
                         "statistics and the SPC queue stay f32)")
    ap.add_argument("--remat", default="full",
                    choices=["full", "tp_out", "none"],
                    help="checkpoint policy at the block-scan boundary "
                         "(tp_out saves post-all-reduce activations)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--rule", default="momentum", choices=list(RULES))
    ap.add_argument("--consistent", action="store_true")
    ap.add_argument("--k-sigma", type=float, default=2.0)
    ap.add_argument("--stop", type=int, default=3)
    ap.add_argument("--n-seqs", type=int, default=64)
    ap.add_argument("--devices", type=int, default=0,
                    help="train on the first N devices of this process "
                         "(0 = all; single-process runs only)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="hybrid engine: devices on the tensor-parallel "
                         "'model' axis (must divide the device count; the "
                         "rest form the 'data' axis)")
    ap.add_argument("--engine", default=None,
                    choices=["hybrid", "pjit", "data-parallel", "async-ps"],
                    help="training engine (default hybrid; 'pjit' is an "
                         "alias for it — see module docstring)")
    ap.add_argument("--data-parallel", action="store_true",
                    help="alias for --engine data-parallel")
    ap.add_argument("--workers", type=int, default=2,
                    help="async-ps: number of worker threads")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="async-ps: SSP bound — a worker may start step k "
                         "only when every worker finished step k-N; 0 = "
                         "lockstep (synchronous schedule)")
    ap.add_argument("--staleness-decay", default="inverse",
                    help="async-ps: w(tau) family[:alpha] — inverse "
                         "(1/(1+a*tau)), exp (e^-a*tau), none")
    ap.add_argument("--chunk-steps", type=int, default=1,
                    help="K>1 = fused engine: K ISGD steps per dispatch via "
                         "lax.scan over the device-resident FCPR ring "
                         "(bit-exact with the per-step engine)")
    ap.add_argument("--device-ring", action="store_true",
                    help="per-step engine fed from the device-resident "
                         "FCPR ring instead of host batches (implied by "
                         "--chunk-steps > 1)")
    ap.add_argument("--schedule", default=None,
                    help="batch-selection policy (repro.sched): "
                         "fcpr | loss-prop | rank, with options as "
                         "family:k=v,... (e.g. loss-prop:eps=0.2).  "
                         "Selection runs on device over the ring; fcpr is "
                         "bit-exact with the default engines; omit for the "
                         "hard-wired FCPR paths")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for crash-consistent full-engine "
                         "checkpoints (atomic .npz, checksummed; "
                         "repro.train.checkpoints)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint cadence in steps (sync engines: saved "
                         "at the first step/chunk boundary past each mark; "
                         "async-ps: every N applied pushes, written under "
                         "the server lock).  0 = never")
    ap.add_argument("--publish-dir", default=None,
                    help="train-and-serve: directory where full-engine "
                         "checkpoints are published for a live serving "
                         "process (atomic LATEST pointer; a "
                         "repro.serve.SnapshotWatcher hot-swaps each one "
                         "between decode steps).  May equal "
                         "--checkpoint-dir")
    ap.add_argument("--publish-every", type=int, default=0,
                    help="publish cadence in steps (0 = inherit "
                         "--checkpoint-every)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest complete checkpoint in "
                         "--checkpoint-dir (a resumed run continues the "
                         "uninterrupted trajectory bit-exactly — "
                         "repro.train.resume_parity)")
    ap.add_argument("--elastic", action="store_true",
                    help="async-ps: evict crashed/deadline-missing workers "
                         "and re-stripe their FCPR shard across survivors "
                         "instead of failing the run")
    ap.add_argument("--deadline", type=float, default=120.0,
                    help="async-ps: heartbeat deadline in seconds — a "
                         "worker blocking the SSP clock without a "
                         "heartbeat for this long is stalled (evicted when "
                         "--elastic, fatal diagnostic otherwise)")
    ap.add_argument("--fault-plan", default=None,
                    help="async-ps: deterministic fault injection spec, "
                         "kind@worker:step[:key=value,...] joined by ';' — "
                         "e.g. 'crash@2:5;hang@1:8:seconds=1.0' "
                         "(repro.fault)")
    ap.add_argument("--verify-pushes", action="store_true",
                    help="async-ps: workers checksum their deltas and the "
                         "server rejects corrupt arrivals (rejected/"
                         "transient pushes retry with backoff)")
    ap.add_argument("--obs-dir", default=None,
                    help="telemetry directory (repro.obs): per-process "
                         "metrics.pN.jsonl with the live SPC control chart, "
                         "counters and events; process 0 folds a merged "
                         "summary.json.  Ingestion only at existing host-"
                         "sync boundaries — zero extra dispatches")
    ap.add_argument("--obs-console-every", type=int, default=0,
                    help="print a one-line obs counter summary every N "
                         "steps (0 = off; needs --obs-dir)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the run into this "
                         "directory (named annotations around the chunk "
                         "scan, psi push, accelerate subproblem, PS fold)")
    ENV.add_process_args(ap)
    args = ap.parse_args(argv)
    ENV.setup_compilation_cache()

    # before any device use: latency-hiding flags + the process group
    ENV.apply_async_collective_flags()
    try:
        topo = ENV.initialize_from_args(args)
    except (ValueError, RuntimeError) as e:
        raise SystemExit(str(e))
    if topo.num_processes > 1 and (args.engine or "hybrid") == "async-ps":
        raise SystemExit("--engine async-ps is host-thread-parallel; it "
                         "does not compose with --coordinator "
                         "multi-process runs")

    if (args.arch is None) == (args.model is None):
        raise SystemExit("pass exactly one of --arch or --model")
    if args.model is not None:
        cfg = zoo_config(args.model, args.tier)
        if args.reduced:
            raise SystemExit("--reduced applies to --arch configs; the zoo "
                             "CPU tier is --tier tiny")
    else:
        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
    from repro.kernels.policy import resolve_kernels
    try:
        kernels = resolve_kernels(args.kernels)
    except RuntimeError as e:
        raise SystemExit(str(e))
    ENV.p0print(f"kernels: {kernels}")
    model = build_model(
        cfg, kernels=kernels,
        param_dtype=jnp.float32 if args.precision == "f32" else jnp.bfloat16,
        remat=args.remat != "none",
        remat_policy="tp_out" if args.remat == "tp_out" else "full")

    data = make_lm_tokens(0, args.n_seqs, args.seq, cfg.vocab_size)
    sampler = FCPRSampler(data, batch_size=args.batch, seed=1)

    rule = RULES[args.rule]()
    icfg = ISGDConfig(n_batches=sampler.n_batches, k_sigma=args.k_sigma,
                      stop=args.stop)
    lr_fn = constant_lr(args.lr)

    engine = args.engine or ("data-parallel" if args.data_parallel
                             else "hybrid")
    if engine == "pjit":
        engine = "hybrid"                 # historical alias, same engine
    obs = _make_observer(args, cfg, icfg, engine)
    try:
        with maybe_profile(args.profile_dir):
            if engine == "async-ps":
                res = run_async_ps(args, cfg, model, sampler, rule, icfg,
                                   lr_fn, obs=obs)
            else:
                res = run_sync(args, cfg, model, sampler, rule, icfg, lr_fn,
                               engine=engine, obs=obs)
    except MeshError as e:
        # the CLI boundary: library validation errors become exit codes
        raise SystemExit(str(e))
    if obs is not None:
        # a resumed run missed the pre-restart pushes: chart only, no
        # reconcile claim
        final = obs.finalize(None if args.resume else res.state,
                             steps=res.steps, wall=res.seconds)
        if ENV.is_coordinator():
            from repro.obs.recorder import write_merged_summary
            write_merged_summary(args.obs_dir)
        ENV.p0print(f"obs: {args.obs_dir} "
                    f"spc_reconciled={final.get('reconciled', 'n/a')} "
                    f"accel_events={final['accel_events']}")
    ENV.p0print(f"done: {res.steps} steps in {res.seconds:.1f}s "
                f"({res.seconds/res.steps*1e3:.0f} ms/step) "
                f"accelerated={int(res.state.accel_count)} "
                f"sub_iters={int(res.state.sub_iters)}")
    res.kernels = kernels
    return res


if __name__ == "__main__":
    main()
