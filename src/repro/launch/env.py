"""Process/runtime environment layer: ``jax.distributed.initialize`` wiring
and XLA flag composition (ROADMAP: multi-host 3-D mesh scale-out).

Everything here must run BEFORE jax initializes its backend — XLA reads
``XLA_FLAGS`` exactly once, and ``jax.distributed.initialize`` must precede
the first device query.  The helpers are therefore pure environment/config
edits with three hard guarantees (pinned by ``tests/test_env.py``):

  * **append, never clobber** — a user-set ``XLA_FLAGS`` survives; our
    flags are appended after it and a flag the user already set is left
    alone (the user's value wins);
  * **idempotent** — calling any helper twice composes to the same
    environment as calling it once (re-entry before
    ``jax.distributed.initialize`` is a no-op);
  * **single init** — :func:`initialize_distributed` initializes the
    process group exactly once and returns the same
    :class:`ProcessTopology` on re-entry.

Flag sets (modeled on the bayespec config exemplar, SNIPPETS.md §1): the
GPU latency-hiding group overlaps async collectives with compute — exactly
the Eq.21 C2 sync-overhead term the paper's batch-size study amortizes, so
on a real cluster these flags move the measured knee.  On CPU the helper
instead selects the gloo cross-process collective implementation, which is
what lets the same-machine multi-process parity harness
(``repro.distributed.multihost_parity``) run real cross-process psums.

CLI wiring: ``add_process_args`` / ``initialize_from_args`` give every
launcher the same ``--coordinator/--num-processes/--process-id`` surface:

    PYTHONPATH=src python -m repro.launch.train ... \
        --coordinator 127.0.0.1:12345 --num-processes 2 --process-id 0
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Mapping, Optional, Sequence

#: GPU async-collective / latency-hiding flags (SNIPPETS.md §1).  Names
#: only here — values are applied via :func:`apply_xla_flags` so a user
#: override of any one of them wins.
GPU_ASYNC_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def _flag_name(flag: str) -> str:
    """``--xla_foo=3`` -> ``--xla_foo`` (XLA flags are name[=value])."""
    return flag.split("=", 1)[0]


def apply_xla_flags(flags: Sequence[str], *, env: Optional[Mapping] = None,
                    override: bool = False) -> str:
    """Append ``flags`` to ``env['XLA_FLAGS']`` without clobbering it.

    A flag whose *name* already appears in the variable is skipped (the
    existing — usually user-set — value wins) unless ``override=True``, in
    which case the existing occurrence is removed and the new value
    appended (later flags win in XLA's parser anyway; removing keeps the
    variable readable).  Both paths are idempotent: re-applying the same
    flags leaves the variable unchanged.  Returns the new value.
    """
    env = os.environ if env is None else env
    current = [f for f in env.get("XLA_FLAGS", "").split() if f]
    have = {_flag_name(f) for f in current}
    for flag in flags:
        name = _flag_name(flag)
        if name in have:
            if not override or flag in current:
                continue
            current = [f for f in current if _flag_name(f) != name]
        current.append(flag)
        have.add(name)
    env["XLA_FLAGS"] = " ".join(current)
    return env["XLA_FLAGS"]


def apply_async_collective_flags(platform: Optional[str] = None, *,
                                 env: Optional[Mapping] = None) -> str:
    """Latency-hiding/async-collective environment for ``platform``
    (default: ``$JAX_PLATFORMS`` or cpu).  GPU gets the SNIPPETS.md §1 flag
    group; CPU/TPU need no XLA flags (CPU cross-process collectives are
    selected in :func:`initialize_distributed` via the gloo config knob,
    not XLA_FLAGS).  Append-only and idempotent like every helper here."""
    env = os.environ if env is None else env
    platform = platform or env.get("JAX_PLATFORMS", "cpu").split(",")[0]
    if platform == "gpu":
        return apply_xla_flags(GPU_ASYNC_FLAGS, env=env)
    return env.get("XLA_FLAGS", "")


def force_host_device_count(n: int, *, env: Optional[Mapping] = None) -> str:
    """Split the host CPU into ``n`` XLA devices (test/parity harnesses).
    Overrides an existing count (forcing is the point) but preserves every
    other flag in the variable."""
    return apply_xla_flags(
        [f"--xla_force_host_platform_device_count={int(n)}"],
        env=env, override=True)


@dataclass(frozen=True)
class ProcessTopology:
    """The process grid a run executes on — recorded by benchmarks
    (``fig8_scaling`` JSON schema) so multi-host cells can't be conflated
    with single-host ones in the Eq.21 fits."""

    process_id: int = 0
    num_processes: int = 1
    coordinator: Optional[str] = None

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


_TOPOLOGY: Optional[ProcessTopology] = None


def initialize_distributed(coordinator: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None,
                           ) -> ProcessTopology:
    """Wire up ``jax.distributed.initialize`` for a multi-process run.

    Single-process (no coordinator, or ``num_processes in (None, 1)``) is a
    no-op that returns the trivial topology — callers can call this
    unconditionally.  On CPU the gloo cross-process collective
    implementation is selected first (the default 'none' cannot execute
    cross-process psums).  Idempotent: a second call returns the topology
    of the first and never re-initializes; a second call with *different*
    arguments raises, because a half-switched process group is undebuggable.
    """
    global _TOPOLOGY
    if coordinator is None and (num_processes or 1) == 1:
        return _TOPOLOGY or ProcessTopology()
    if num_processes is None or process_id is None:
        raise ValueError("--coordinator needs both --num-processes and "
                         "--process-id")
    topo = ProcessTopology(process_id=int(process_id),
                           num_processes=int(num_processes),
                           coordinator=coordinator)
    if _TOPOLOGY is not None:
        if _TOPOLOGY != topo:
            raise RuntimeError(
                f"jax.distributed already initialized as {_TOPOLOGY}; "
                f"cannot re-initialize as {topo}")
        return _TOPOLOGY
    import jax
    if os.environ.get("JAX_PLATFORMS", "cpu").split(",")[0] in ("", "cpu"):
        # cross-process CPU collectives need a real implementation
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=int(num_processes),
                               process_id=int(process_id))
    _TOPOLOGY = topo
    return topo


def topology() -> ProcessTopology:
    """The current process topology as jax sees it (valid after backend
    init; falls back to the recorded init arguments before that)."""
    import jax
    try:
        return ProcessTopology(process_id=jax.process_index(),
                               num_processes=jax.process_count(),
                               coordinator=(_TOPOLOGY.coordinator
                                            if _TOPOLOGY else None))
    except Exception:
        return _TOPOLOGY or ProcessTopology()


def is_coordinator() -> bool:
    """True on the process that owns logging/checkpoint-writing duties."""
    return topology().is_coordinator


def p0print(*args, **kwargs) -> None:
    """Print only on process 0 — delegates to the obs console sink
    (``repro.obs.console.CONSOLE``), the one mechanism that keeps non-zero
    processes quiet for progress lines and warnings alike."""
    from repro.obs.console import CONSOLE
    CONSOLE.print(*args, **kwargs)


#: The persistent compilation cache's directory when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: fixed and inside the checkout
#: (git-ignored), so a later run of the same checkout finds its entries.
DEFAULT_COMPILATION_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def setup_compilation_cache(env: Optional[Mapping] = None) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Call at a program's start, never at import.  A set
    ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads the variable itself and
    no other directory is set in code.  Otherwise the cache goes to
    :data:`DEFAULT_COMPILATION_CACHE_DIR` — never a temp dir or a path
    keyed on the pid or the time, which no later run would find."""
    env = os.environ if env is None else env
    if env.get("JAX_COMPILATION_CACHE_DIR"):
        return env["JAX_COMPILATION_CACHE_DIR"]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILATION_CACHE_DIR)
    return DEFAULT_COMPILATION_CACHE_DIR


def add_process_args(parser) -> None:
    """The shared ``--coordinator/--num-processes/--process-id`` CLI
    surface (launch/train, parity harnesses, benchmarks)."""
    parser.add_argument("--coordinator", default=None,
                        help="host:port of process 0's coordination "
                             "service; presence switches the run to "
                             "multi-process (jax.distributed.initialize)")
    parser.add_argument("--num-processes", type=int, default=None,
                        help="total process count of the multi-process run")
    parser.add_argument("--process-id", type=int, default=None,
                        help="this process's index in [0, num_processes)")


def initialize_from_args(args) -> ProcessTopology:
    """``add_process_args`` namespace -> initialized topology (no-op when
    the run is single-process)."""
    return initialize_distributed(coordinator=args.coordinator,
                                  num_processes=args.num_processes,
                                  process_id=args.process_id)
