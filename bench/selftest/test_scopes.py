"""The traced window by layer (``bench/scopes.py``): the scope matcher on
the op paths the program gives, the XSpace metadata reader on a hand-built
message, the shares on a hand-made trace whose answers are known, and the
reader on the chip traces of ``bench/selftest/data``."""
import gzip
import shutil
import struct
import tempfile
from pathlib import Path

import pytest

from bench import scopes as SC
from bench import trace as TR
from bench.trace import Event, TraceData

DATA = Path(__file__).resolve().parent / "data"
SCAN = "jit(chunk_fn)/obs/chunk_scan/while/body/closed_call/"
FWD = SCAN + "jvp()/while/body/closed_call/"
BWD = SCAN + "transpose(jvp())/while/body/closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"


def test_scope_matches_whole_components_plain_or_wrapped():
    assert SC.in_scope(FWD + "obs/attn/dot_general", "obs/attn")
    assert SC.in_scope(SCAN + "transpose(jvp(obs/lm_head))/dot_general",
                       "obs/lm_head")
    assert SC.in_scope(SCAN + "jvp(obs/lm_head)", "obs/lm_head")
    assert SC.in_scope("obs/update", "obs/update")
    assert not SC.in_scope(FWD + "obs/attention/dot_general", "obs/attn")
    assert not SC.in_scope(FWD + "xobs/attn/dot_general", "obs/attn")
    assert not SC.in_scope(FWD + "jit(flash_attention)/pallas_call",
                           "obs/attn")


# ---------------------------------------------------------------------------
# the wire-format reader, on an XSpace built by hand
# ---------------------------------------------------------------------------
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """(field, value): an int is a varint, a float a fixed 64-bit double,
    str and bytes length-delimited."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        elif isinstance(v, float):
            out += _varint(f << 3 | 1) + struct.pack("<d", v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, stat_names, events):
    """An XPlane: stat metadata {id: name}, event metadata [(id, name,
    [XStat bytes])]."""
    fields = [(1, 7), (2, name)]
    fields += [(5, _msg((1, k), (2, _msg((1, k), (2, v)))))
               for k, v in stat_names.items()]
    fields += [(4, _msg((1, i), (2, _msg((1, i), (2, n),
                                         *[(5, s) for s in stats]))))
               for i, n, stats in events]
    return _msg(*fields)


def test_op_scopes_reads_tf_op_by_string_or_reference(tmp_path):
    stats = {1: "tf_op", 2: "flops", 3: FWD + "obs/attn/dot_general"}
    tpu0 = _plane("/device:TPU:0", stats, [
        (10, "%fusion.1 = f32[] op()",
         [_msg((1, 2), (4, 7)), _msg((1, 1), (5, FWD + "obs/mlp/add"))]),
        (11, "%fusion.2 = f32[] op()", [_msg((1, 1), (7, 3))]),
        (12, "%copy.3 = f32[] copy()", [_msg((1, 2), (2, 2.5))])])
    tpu1 = _plane("/device:TPU:1", {1: "tf_op"}, [
        (10, "%fusion.1 = f32[] op()", [_msg((1, 1), (5, "other/path"))])])
    host = _plane("/host:CPU", {1: "tf_op"}, [
        (1, "%fusion.2 = f32[] op()", [_msg((1, 1), (5, "host/path"))])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, tpu0), (1, tpu1), (1, host), (4, "localhost")))
    assert SC.op_scopes(str(path)) == {
        "%fusion.1 = f32[] op()": FWD + "obs/mlp/add",
        "%fusion.2 = f32[] op()": FWD + "obs/attn/dot_general",
        "%copy.3 = f32[] copy()": ""}
    # the same text with two different paths is ambiguous
    assert SC.op_scopes(str(path), chips=2)["%fusion.1 = f32[] op()"] == ""


# ---------------------------------------------------------------------------
# the shares, on a hand-made trace
# ---------------------------------------------------------------------------
def ev(name, start, end):
    return Event(f"%{name} = f32[] op()", start, end)


def hand_trace():
    # window [0, 10]; a while op [0.5, 9.5] around the rest (left out);
    # attention forward [1, 3], overlapping the MLP forward [2, 4]; the
    # attention backward [4, 7], of which [4, 6] is its recompute; the MLP
    # backward's recompute [6.5, 7.5]; LM head [7.5, 8.5]; update [8.5, 9];
    # the SPC push [9, 9.2]; an op with no path [9.2, 9.3]
    paths = {
        "while.1": SCAN + "while",
        "fusion.2": FWD + "obs/attn/dot_general",
        "fusion.3": FWD + "obs/mlp/dot_general",
        "flash_attention.4": REMAT + "obs/attn/jit(flash_attention)/pallas_call",
        "fusion.5": BWD + "obs/attn/dot_general",
        "fusion.6": REMAT + "obs/mlp/dot_general",
        "jvp_jit_fused_xent__.7": SCAN + "jvp(obs/lm_head)/jit(fused_xent)/pallas_call",
        "fusion.8": SCAN + "transpose(jvp(obs/lm_head))/dot_general",
        "fusion.9": SCAN + "obs/update/add",
        "fusion.10": SCAN + "obs/psi_push/scatter",
        "copy.11": "",
    }
    spans = [(0.5, 9.5), (1, 3), (2, 4), (4, 6), (6, 7), (6.5, 7.5),
             (7.5, 8), (8, 8.5), (8.5, 9), (9, 9.2), (9.2, 9.3)]
    ops = [ev(n, s, e) for n, (s, e) in zip(paths, spans)]
    t = TraceData(ops=[ops], host=[Event("bench/epoch", 0, 10)], t0=0.0,
                  t1=10.0)
    return t, {e.name: paths[e.op] for e in ops}


def test_layer_shares_on_a_hand_trace():
    t, scopes = hand_trace()
    got = {k: SC.share(t, scopes, pick) for k, pick in SC.SHARES.items()}
    assert got == pytest.approx({
        "step.attention_fwd_share": 20.0, "step.attention_bwd_share": 30.0,
        "step.mlp_share": 30.0, "step.lm_head_share": 10.0,
        "step.recompute_share": 30.0, "step.update_share": 5.0})
    assert SC.coverage(t, scopes, SC.LAYERS) == pytest.approx(100 * 8 / 8.3)
    assert SC.coverage(t, scopes, SC.LAYERS + (SC.PSI_PUSH,)) == \
        pytest.approx(100 * 8.2 / 8.3)
    left = SC.unscoped(t, scopes, SC.LAYERS)
    assert [k for k, _, _ in left] == ["fusion", "copy"]
    assert [s for _, s, _ in left] == pytest.approx([0.2, 0.1])


def test_layer_shares_are_none_without_the_scopes():
    t, scopes = hand_trace()
    bare = {k: v.replace("obs/", "x/") for k, v in scopes.items()}
    for k, pick in SC.SHARES.items():
        if k != "step.recompute_share":
            assert SC.share(t, bare, pick) is None, k
    assert SC.share(t, {}, SC.SHARES["step.recompute_share"]) is None


def test_idle_time_in_host_spans_after_the_clock_shift():
    # two chunk modules whose device clock reads 0.5 s early: the first
    # starts 0.5 s before its dispatch span, the second 0.2 s before
    t = TraceData(ops=[[ev("fusion.1", 0.5, 4.0), ev("fusion.2", 4.8, 8.5)]],
                  host=[Event("bench/epoch", 0, 10)], t0=0.0, t1=10.0)
    host = [Event("train/dispatch", 1.0, 1.1), Event("train/fetch", 1.1, 4.9),
            Event("train/log", 4.9, 5.0), Event("train/dispatch", 5.0, 5.1),
            Event("train/fetch", 5.1, 9.1), Event("train/log", 9.1, 9.6)]
    modules = [Event("jit_chunk_fn(1)", 0.5, 4.0),
               Event("jit_chunk_fn(1)", 4.8, 8.5)]
    got = SC.idle_in_host_spans(t, host, modules)
    assert got["module_start_minus_dispatch_s"] == pytest.approx([-0.5, -0.2])
    assert got["shift_s"] == pytest.approx(0.5)
    # shifted busy [1, 4.5] and [5.3, 9]: idle [0, 1], [4.5, 5.3], [9, 10];
    # inside a host span: [4.5, 5.3] and [9, 9.6]
    assert got["idle_s"] == pytest.approx(2.8)
    assert got["idle_in_host_spans"] == pytest.approx(100 * 1.4 / 2.8)


# ---------------------------------------------------------------------------
# chip traces
# ---------------------------------------------------------------------------
def unpacked(name, fn):
    d = tempfile.mkdtemp()
    try:
        raw = Path(d) / "t.xplane.pb"
        with gzip.open(DATA / f"{name}.xplane.pb.gz") as f, \
                open(raw, "wb") as g:
            shutil.copyfileobj(f, g)
        return fn(str(raw))
    finally:
        shutil.rmtree(d)


def test_trace_without_layer_scopes():
    """The first recorded trace, of a program with no layer scopes: every
    op of the window is in the metadata, and all but 19.3 ms of op time
    has a path; the layer shares read nothing, remat's recompute 28.8%."""
    def check(raw):
        t = TR.load(str(Path(raw).parent), 1)
        scopes = SC.op_scopes(raw)
        assert {e.name for e in t.ops[0]} <= set(scopes)
        r = SC.report(raw)
        assert r["untagged_s"] == pytest.approx(0.0193, abs=5e-4)
        assert r["shares"]["step.recompute_share"] == pytest.approx(28.79,
                                                                    abs=0.01)
        assert [k for k, v in r["shares"].items() if v is not None] == \
            ["step.recompute_share"]
        assert r["coverage_layers"] == 0.0
    unpacked("internlm2-1.8b.hard1of16", check)


def test_trace_with_layer_scopes():
    """A trace of the program with the layer scopes (cell 1, seed 13, one
    traced epoch on a TPU v5e): every share read, within [0, 100], and as
    recorded; attention forward + backward, MLP, LM head and update cover
    at least 95% of chip 0's busy time; both chunk modules start before
    their ``train/dispatch`` span on the device clock, and after the shift
    at least 90% of the idle time lies inside a ``train/*`` span."""
    def check(raw):
        r = SC.report(raw)
        assert r["shares"] == pytest.approx({
            "step.attention_fwd_share": 25.8844,
            "step.attention_bwd_share": 48.9320, "step.mlp_share": 13.0133, "step.lm_head_share": 8.5205,
            "step.recompute_share": 28.7871, "step.update_share": 1.8004},
            abs=1e-3)
        assert all(0.0 <= v <= 100.0 for v in r["shares"].values())
        assert r["coverage_layers"] >= 95.0
        host = r["host"]
        assert len(host["module_start_minus_dispatch_s"]) == 2
        assert all(o < 0 for o in host["module_start_minus_dispatch_s"])
        assert host["idle_in_host_spans"] >= 90.0
    unpacked("internlm2-1.8b.hard1of16.scoped", check)
