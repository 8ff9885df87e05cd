"""Step timing, throughput accounting, and the measured-vs-estimated wall
contract — plus optional ``jax.profiler`` trace capture.

``StepTimer`` replaces the copy-pasted ``t0 = time.perf_counter() ... dt``
runner blocks: named spans accumulate wall seconds, carry the
``TrainLog``-style *estimated* flag (chunk-end stacking, un-synced
dispatch timing, overlapping async pushes), and compute steps/s /
examples/s / dispatch counts in one place.

:func:`require_measured_walls` is the shared refuse-to-fit guard — Eq. 21
timing fits (``fig8_batch_size``, ``fig8_scaling``) must never consume
``wall_est`` entries.

Profiler hooks (all lazy-import jax, so this module stays importable in
the jax-free sweep parents):

* :func:`maybe_profile` — context manager around a run; starts a
  ``jax.profiler`` trace when ``--profile-dir`` is set, else no-op.
* :func:`annotate` — host-side ``TraceAnnotation`` span (PS fold, decode
  step, the phases of the launcher's chunk loop) visible on the trace
  timeline.
* :func:`named_scope` — ``jax.named_scope`` for *traced* code: pure
  metadata on the jaxpr (it reaches each compiled op's ``op_name``, and the
  device trace carries it as the op's ``tf_op``), zero runtime cost, so it
  is safe inside the fused hot path.

The scopes the program puts on traced ops and the host spans of the
launcher's chunk loop are listed once, below; the program names them only
through these constants.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional, Sequence


class EstimatedWallError(RuntimeError):
    """A timing fit was about to consume estimated (non-measured) walls."""


def require_measured_walls(wall_est: Sequence[bool], context: str = "") -> None:
    """Refuse to proceed when any wall-clock entry is flagged estimated.

    ``wall_est`` is a sequence of flags, True = estimated (``TrainLog``
    semantics: step_sync=False per-step timing, fused-chunk stacking, or
    overlapping async pushes).  Raises :class:`EstimatedWallError` naming
    the offending fraction — estimated walls silently feeding an Eq.21
    C1/C2 fit is exactly the failure mode this guards."""
    flags = [bool(x) for x in wall_est]
    n_bad = sum(flags)
    if n_bad:
        where = context or "timing fit"
        raise EstimatedWallError(
            f"{where}: refusing to fit on estimated walls — {n_bad}/{len(flags)} "
            "entries have wall_est=True (per-step timing without step_sync, "
            "fused-chunk dispatch estimates, or overlapping async pushes). "
            "Re-measure with synced per-step walls.")


class StepTimer:
    """Named accumulating wall-clock spans + throughput derivation.

    >>> timer = StepTimer()
    >>> with timer.span("train"):
    ...     run()
    >>> timer.throughput("train", steps=n)  # {'wall_s': ..., 'steps_per_s': ...}

    Spans re-entered accumulate (the serve drain loop times many small
    spans under one name).  ``estimated=True`` marks a span's wall as
    non-measured; :meth:`throughput` propagates the flag so downstream
    fits can refuse it via :func:`require_measured_walls`."""

    def __init__(self, recorder=None, clock=time.perf_counter):
        self.recorder = recorder
        self._clock = clock
        self._acc: Dict[str, float] = {}
        self._est: set = set()

    @contextlib.contextmanager
    def span(self, name: str, *, estimated: bool = False):
        t0 = self._clock()
        try:
            yield self
        finally:
            self._acc[name] = self._acc.get(name, 0.0) + (self._clock() - t0)
            if estimated:
                self._est.add(name)

    def add(self, name: str, seconds: float, *, estimated: bool = False) -> None:
        """Fold an externally measured duration into a span."""
        self._acc[name] = self._acc.get(name, 0.0) + float(seconds)
        if estimated:
            self._est.add(name)

    def seconds(self, name: str) -> float:
        return self._acc.get(name, 0.0)

    def estimated(self, name: str) -> bool:
        return name in self._est

    def throughput(self, name: str, *, steps: int = 0, examples: int = 0,
                   dispatches: int = 0) -> dict:
        """Derive rates for a span; emits gauges + one event when a
        recorder is attached."""
        dt = self.seconds(name)
        out = {"wall_s": dt, "wall_est": self.estimated(name)}
        if dispatches:
            out["dispatches"] = int(dispatches)
        if dt > 0.0:
            if steps:
                out["steps_per_s"] = steps / dt
            if examples:
                out["examples_per_s"] = examples / dt
            if dispatches:
                out["dispatches_per_s"] = dispatches / dt
        if self.recorder is not None:
            for key in ("steps_per_s", "examples_per_s"):
                if key in out:
                    self.recorder.gauge(f"time/{name}/{key}", out[key])
            self.recorder.event(f"time/{name}", **out)
        return out


# ------------------------------------------------------------- profiler

# scopes on traced ops (``named_scope``)
CHUNK_SCAN = "obs/chunk_scan"    # the fused K-step scan
ATTN = "obs/attn"                # attention sub-block: ln1, mixer, residual
SSM = "obs/ssm"                  # the same for an SSM mixer
SSD = "obs/ssd"                  # its chunked SSD scan, inside SSM
MLP = "obs/mlp"                  # MLP sub-block: ln2, mlp, residual
LM_HEAD = "obs/lm_head"          # final norm, head and loss
UPDATE = "obs/update"            # the base rule's apply
PSI_PUSH = "obs/psi_push"        # SPC queue push and control limit
ACCELERATE = "obs/accelerate"    # the conservative subproblem

# host spans of the launcher's chunk loop (``annotate``)
TRAIN_DISPATCH = "train/dispatch"      # the chunk call
TRAIN_FETCH = "train/fetch"            # device_get of the chunk's metrics
TRAIN_OBS = "train/obs"                # obs ingest of those metrics
TRAIN_LOG = "train/log"                # the per-chunk print
TRAIN_CHECKPOINT = "train/checkpoint"  # maybe_save


@contextlib.contextmanager
def maybe_profile(profile_dir: Optional[str]):
    """Capture a ``jax.profiler`` trace into ``profile_dir`` when set
    (``--profile-dir``); no-op (and no jax import) when None."""
    if not profile_dir:
        yield
        return
    import jax
    jax.profiler.start_trace(profile_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Host-side trace annotation (``jax.profiler.TraceAnnotation``) for
    un-jitted spans: PS fold, decode step, checkpoint IO.  Cheap enough to
    leave on unconditionally."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def named_scope(name: str):
    """``jax.named_scope`` — name traced operations (the scopes above) on
    profiles/HLO at zero runtime cost."""
    import jax
    return jax.named_scope(name)
