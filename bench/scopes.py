"""The traced window by layer: op scopes from the trace's own op metadata.

``bench/trace.py::load`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData``, which names a device op event by its HLO text
and gives the event's own stats only.  The op's name-scope path is in the
device plane's *event metadata*, as the stat ``tf_op``
(``jit(chunk_fn)/obs/chunk_scan/while/body/closed_call/jvp()/while/body/
closed_call/obs/attn/dot_general``); it annotates the same events, so it
shares their clock.  ``op_scopes`` decodes it from the ``XSpace`` protobuf
with a small wire-format reader of the few fields it needs, so neither
TensorFlow nor ``google.protobuf`` is needed.

The program's scope strings (``src/repro/obs/timing.py``) are written out
here and not imported, so that the yardstick does not move with the
program; ``tests/test_obs_scopes.py`` pins them and the forms in which they
reach an op's path: a layer scope inside the scan over layers stays plain
(``jvp()/while/body/closed_call/obs/attn``, backward
``transpose(jvp())/…/checkpoint/obs/attn``, remat's recompute
``…/checkpoint/rematted_computation/obs/attn``), the LM head outside it is
wrapped (``jvp(obs/lm_head)``, ``transpose(jvp(obs/lm_head))``).

    python3 bench/scopes.py <trace .xplane.pb or .xplane.pb.gz>

prints one JSON object for a trace of ``bench/run.py --trace 1`` (kept by
``bench/selftest/record_trace.py``): each layer's share of the traced
window on chip 0, how much of chip 0's busy time the scopes cover and what
they leave out by op kind, each chunk's device module start minus its
``train/dispatch`` host span's start, and the share of chip 0's idle time
that lies inside a ``train/*`` host span once the device clock is shifted
by the smallest amount that starts no module before its dispatch.
"""
from __future__ import annotations

import gzip
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.trace import Event, leaf_ops, union  # noqa: E402

ATTN, MLP, LM_HEAD, UPDATE = "obs/attn", "obs/mlp", "obs/lm_head", "obs/update"
PSI_PUSH, ACCELERATE = "obs/psi_push", "obs/accelerate"
BACKWARD = "transpose("
RECOMPUTE = "rematted_computation/"
LAYERS = (ATTN, MLP, LM_HEAD, UPDATE)
HOST_PREFIX = "train/"
CHUNK_MODULE = "jit_chunk_fn"


def in_scope(path: str, scope: str) -> bool:
    """``scope`` is a component of the op path ``path``, plain or wrapped by
    a transform (``jvp(obs/lm_head)``)."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)",
                     path) is not None


SHARES = {
    "step.attention_fwd_share":
        lambda p: in_scope(p, ATTN) and BACKWARD not in p,
    "step.attention_bwd_share": lambda p: in_scope(p, ATTN) and BACKWARD in p,
    "step.mlp_share": lambda p: in_scope(p, MLP),
    "step.lm_head_share": lambda p: in_scope(p, LM_HEAD),
    "step.recompute_share": lambda p: RECOMPUTE in p,
    "step.update_share": lambda p: in_scope(p, UPDATE),
}


# ---------------------------------------------------------------------------
# XSpace wire format: XSpace.planes = 1; XPlane.name = 2, .event_metadata =
# 4, .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, a string value
# str_value = 5 or ref_value = 7 (a stat metadata id whose name is the
# string); the other fields are skipped
# ---------------------------------------------------------------------------
def _fields(buf):
    """(field number, value) of a message: an int for a varint, a
    memoryview for a length-delimited field, bytes for a fixed one."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        out = shift = 0
        while True:
            c = buf[i]
            i += 1
            out |= (c & 0x7F) << shift
            shift += 7
            if c < 0x80:
                return out

    while i < n:
        key = varint()
        wire = key & 7
        if wire == 0:
            v = varint()
        elif wire == 2:
            size = varint()
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")
        yield key >> 3, v


def _message(buf) -> dict:
    out = {}
    for f, v in _fields(buf):
        out.setdefault(f, []).append(v)
    return out


def _map_entries(buf_list):
    for entry in buf_list:
        m = _message(entry)
        yield (m.get(1, [0])[0], _message(m[2][0]) if 2 in m else {})


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _str_stat(stat: dict, stat_names: dict) -> str:
    """A string stat's value: inline, or a reference to a stat metadata
    entry whose name is the string."""
    if 5 in stat:
        return _text(stat[5][0])
    return stat_names.get(stat[7][0], "") if 7 in stat else ""


def op_scopes(xplane: str, chips: int = 1) -> dict:
    """{HLO text of a device op (the event name ``ProfileData`` gives):
    its ``tf_op``} over the planes ``/device:TPU:<i>``, ``i < chips``.  A
    text met with two different paths maps to ""."""
    with open(xplane, "rb") as f:
        space = memoryview(f.read())
    scopes = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        p = _message(plane)
        m = re.fullmatch(r"/device:TPU:(\d+)", _text(p.get(2, [b""])[0]))
        if not m or int(m.group(1)) >= chips:
            continue
        stat_names = {k: _text(v.get(2, [b""])[0])
                      for k, v in _map_entries(p.get(5, []))}
        for _, meta in _map_entries(p.get(4, [])):
            name = _text(meta.get(2, [b""])[0])
            path = ""
            for s in meta.get(5, []):
                stat = _message(s)
                if stat_names.get(stat.get(1, [0])[0]) == "tf_op":
                    path = _str_stat(stat, stat_names)
            if scopes.setdefault(name, path) != path:
                scopes[name] = ""
    return scopes


# ---------------------------------------------------------------------------
# the window by layer
# ---------------------------------------------------------------------------
def picked(t, scopes: dict, pick):
    """The leaf ops of chip 0 whose path satisfies ``pick``."""
    memo = {name: pick(scopes.get(name, "")) for name in {e.name for e in t.ops[0]}}
    return [e for e in leaf_ops(t.ops[0]) if memo[e.name]]


def share(t, scopes: dict, pick):
    """Per cent of the traced window covered by the leaf ops of chip 0 whose
    path satisfies ``pick``; None when no op does."""
    evs = picked(t, scopes, pick)
    return 100.0 * t.time(evs) / t.window_s if evs else None


def in_any(layer_scopes):
    """A ``pick`` for ops in one of ``layer_scopes``."""
    return lambda path: any(in_scope(path, s) for s in layer_scopes)


def coverage(t, scopes: dict, layer_scopes) -> float:
    """Per cent of chip 0's busy time (the union of its leaf ops) covered
    by ops in one of ``layer_scopes``."""
    return (100.0 * t.time(picked(t, scopes, in_any(layer_scopes)))
            / t.time(leaf_ops(t.ops[0])))


def unscoped(t, scopes: dict, layer_scopes, top: int = 10):
    """What ``layer_scopes`` leave out, by op kind: [kind, seconds, an op
    path] for the ``top`` kinds by time."""
    inside = in_any(layer_scopes)
    by = {}
    for e in picked(t, scopes, lambda p: not inside(p)):
        s, p = by.get(e.kind, (0.0, scopes.get(e.name, "")))
        by[e.kind] = (s + t.time([e]), p)
    return [[k, s, p] for k, (s, p) in
            sorted(by.items(), key=lambda kv: -kv[1][0])[:top]]


# ---------------------------------------------------------------------------
# host spans against the device clock
# ---------------------------------------------------------------------------
def host_and_modules(xplane: str, t):
    """The ``train/*`` host spans and the chunk program's module events of
    chip 0 that overlap the window of ``t``, each sorted by start."""
    from jax.profiler import ProfileData
    host, modules = [], []
    for plane in ProfileData.from_file(xplane).planes:
        on_host = plane.name.startswith("/host:")
        for line in plane.lines:
            on_chip0 = (plane.name == "/device:TPU:0"
                        and line.name == "XLA Modules")
            if not (on_host or on_chip0):
                continue
            for e in line.events:
                ev = Event(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                if not (ev.end > t.t0 and ev.start < t.t1):
                    continue
                if on_chip0 and e.name.startswith(CHUNK_MODULE):
                    modules.append(ev)
                elif on_host and e.name.startswith(HOST_PREFIX):
                    host.append(ev)
    return (sorted(host, key=lambda e: e.start),
            sorted(modules, key=lambda e: e.start))


def idle_in_host_spans(t, host, modules):
    """Each chunk's module start minus its ``train/dispatch`` start (s), and
    the share (%) of chip 0's idle time inside a host span after the device
    clock is shifted by the smallest amount that starts no module before
    its dispatch."""
    dispatch = [h for h in host if h.name == HOST_PREFIX + "dispatch"]
    offsets = [m.start - d.start for m, d in zip(modules, dispatch)]
    shift = max([0.0] + [-o for o in offsets])
    ops = [Event(e.name, e.start + shift, e.end + shift) for e in t.ops[0]]
    gaps, cur = [], t.t0
    for e in sorted(ops, key=lambda e: e.start):
        if e.start > cur:
            gaps.append(Event("gap", cur, min(e.start, t.t1)))
        cur = max(cur, e.end)
    if cur < t.t1:
        gaps.append(Event("gap", cur, t.t1))
    idle = sum(g.dur for g in gaps)
    inside = sum(union(host, g.start, g.end) for g in gaps)
    return {"module_start_minus_dispatch_s": offsets, "shift_s": shift,
            "idle_s": idle,
            "idle_in_host_spans": 100.0 * inside / idle if idle else None}


def report(xplane: str) -> dict:
    """The traced window of the trace file ``xplane`` by layer and by host
    span (one chip), with the seconds ``op_scopes`` took."""
    from bench import trace as TR
    d = tempfile.mkdtemp()
    try:
        os.symlink(os.path.abspath(xplane), os.path.join(d, "t.xplane.pb"))
        t = TR.load(d, 1)
    finally:
        shutil.rmtree(d)
    t_decode = time.perf_counter()
    scopes = op_scopes(xplane)
    decode_s = time.perf_counter() - t_decode
    host, modules = host_and_modules(xplane, t)
    everything = LAYERS + (PSI_PUSH, ACCELERATE)
    return {
        "window_s": t.window_s, "busy_s": t.busy(0),
        "shares": {k: share(t, scopes, pick) for k, pick in SHARES.items()},
        "coverage_layers": coverage(t, scopes, LAYERS),
        "coverage_all_scopes": coverage(t, scopes, everything),
        "unscoped_by_kind": unscoped(t, scopes, LAYERS),
        "untagged_s": t.time(picked(t, scopes, lambda p: not p)),
        "host": idle_in_host_spans(t, host, modules),
        "host_spans": [[h.name, h.start - t.t0, h.dur] for h in host],
        "decode_s": decode_s,
    }


def main(path: str):
    if path.endswith(".gz"):
        d = tempfile.mkdtemp()
        try:
            raw = os.path.join(d, "t.xplane.pb")
            with gzip.open(path) as f, open(raw, "wb") as g:
                shutil.copyfileobj(f, g)
            out = report(raw)
        finally:
            shutil.rmtree(d)
    else:
        out = report(path)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
