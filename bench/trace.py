"""From a ``jax.profiler`` trace to what the per-layer readers need.

``load`` reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``: the op
events of each chip used (the ``XLA Ops`` line of plane
``/device:TPU:<i>``, and the in-flight spans of async ops from its ``Async
XLA Ops`` line) and the harness's host spans (``bench/epoch`` around each
traced epoch, ``bench/dispatch`` around each chunk dispatch).  The traced
window is the span of the ``bench/epoch`` events.

An op event is named by its HLO instruction (``%fusion.868 = ...``), with
no name-scope path; a control-flow op (``while``, ``conditional``,
``call``) spans the ops of its body, so times are taken as unions of
intervals.
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import re

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
CONTROL_FLOW = ("while", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds, on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The HLO instruction name (``fusion.868``)."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def kind(self) -> str:
        """The instruction name without its numeric suffix."""
        return re.sub(r"[.\d]+$", "", self.op)


def union(events, t0=-math.inf, t1=math.inf) -> float:
    """Seconds covered by the union of ``events``' intervals in [t0, t1]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, t in sorted((max(e.start, t0), min(e.end, t1)) for e in events):
        if t <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, t
        else:
            cur_e = max(cur_e, t)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


@dataclasses.dataclass
class TraceData:
    ops: list             # per chip: [Event], sorted by start, in the window
    host: list            # host spans of the harness: [Event]
    t0: float
    t1: float
    async_ops: list = dataclasses.field(default_factory=list)  # per chip

    def time(self, events) -> float:
        """Seconds of the window covered by ``events``."""
        return union(events, self.t0, self.t1)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy(self, chip: int = 0) -> float:
        """Seconds of the window in which some op ran on ``chip``."""
        return self.time(self.ops[chip])

    def busy_s(self) -> float:
        return sum(self.busy(c) for c in range(len(self.ops))) / len(self.ops)

    def gaps(self, chip: int = 0):
        """Idle intervals of ``chip`` inside the window: [(start, end)]."""
        out, cur = [], self.t0
        for e in self.ops[chip]:
            if e.start > cur:
                out.append((cur, min(e.start, self.t1)))
            cur = max(cur, e.end)
        if cur < self.t1:
            out.append((cur, self.t1))
        return [g for g in out if g[1] - g[0] > 1e-6]


def xplane_path(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(trace_dir: str, chips: int) -> TraceData:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path(trace_dir))
    host, ops, async_ops = [], {}, {}

    def events(line):
        return [Event(e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events]

    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = events(line)
                elif line.name == ASYNC_LINE:
                    async_ops[int(m.group(1))] = events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        host.append(Event(e.name, e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns) * 1e-9))
    if sorted(ops) != list(range(chips)):
        raise RuntimeError(f"trace has op lines for chips {sorted(ops)}, "
                           f"expected {chips}")
    epochs = [e for e in host if e.name == "bench/epoch"]
    if not epochs:
        raise RuntimeError("trace has no bench/epoch span")
    t0, t1 = min(e.start for e in epochs), max(e.end for e in epochs)
    def window(evs):
        return sorted((e for e in evs if e.end > t0 and e.start < t1),
                      key=lambda e: e.start)

    return TraceData(ops=[window(ops[c]) for c in range(chips)],
                     host=sorted(host, key=lambda e: e.start), t0=t0, t1=t1,
                     async_ops=[window(async_ops.get(c, []))
                                for c in range(chips)])


def leaf_ops(ops):
    """Ops that are not control flow around other ops."""
    return [e for e in ops if e.kind not in CONTROL_FLOW]


def breakdown(t: TraceData) -> dict:
    """The ten device op kinds (control flow left out) that took most time
    on chip 0, and the ten longest idle gaps of chip 0, named by the host
    span they fall in."""
    by = {}
    for e in leaf_ops(t.ops[0]):
        by[e.kind] = by.get(e.kind, 0.0) + (min(e.end, t.t1)
                                            - max(e.start, t.t0))
    top = sorted(by.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    for s, e in sorted(t.gaps(0), key=lambda g: g[0] - g[1])[:10]:
        mid = 0.5 * (s + e)
        inside = [h.name for h in t.host if h.start <= mid <= h.end]
        name = ("dispatch" if "bench/dispatch" in inside else
                "metric fetch / host loop" if "bench/epoch" in inside else
                "outside the epoch")
        gaps.append([name, e - s])
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": gaps}


def passes(steps: int, trips: int) -> int:
    """Forward+backward passes over the global batch: each ISGD step's main
    pass and each subproblem trip (one pass on its step's batch).  The one
    count behind both ``tokens_per_s`` and ``mfu``."""
    return steps + trips


@dataclasses.dataclass
class Context:
    """What a per-layer reader gets: the cell (its configuration and
    traffic as the files give them), the chip's peaks, the traced window,
    and the program's counters over it (ISGD steps and subproblem trips)."""
    cell: object
    peaks: dict
    trace: TraceData
    steps: int
    trips: int

    @property
    def passes(self) -> int:
        return passes(self.steps, self.trips)

    @property
    def model(self) -> dict:
        return self.cell.config["model"]

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


def kernel_calls(t: TraceData, pattern: str, chip: int = 0):
    """Op events of ``chip`` whose instruction name matches ``pattern``
    (a Pallas kernel's custom call is named after its jitted wrapper)."""
    rx = re.compile(pattern)
    return [e for e in t.ops[chip] if rx.search(e.op)]


def roofline_share(t: TraceData, peaks: dict, calls, flops: float,
                   nbytes: float):
    """Per cent of the least time of ``len(calls)`` calls of ``flops`` and
    ``nbytes`` each over their summed device time, and which bound
    applies; None when there are no calls."""
    if not calls:
        return None, None
    t_flop = flops / peaks["bf16_flops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    spent = sum(e.dur for e in calls)
    bound = "compute" if t_flop >= t_mem else "memory"
    return 100.0 * len(calls) * max(t_flop, t_mem) / spent, bound
