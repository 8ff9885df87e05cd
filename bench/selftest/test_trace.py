"""The reduction from a trace to the per-layer metrics: on hand-made traces
whose answers are known, and on small traces recorded on the chip
(``bench/selftest/data``, written by ``record_trace.py``)."""
import gzip
import json
import shutil
import tempfile
from pathlib import Path

import pytest

from bench import harness as H
from bench import trace as TR
from bench.trace import Context, Event, TraceData

DATA = Path(__file__).resolve().parent / "data"
PEAKS = H.load_json(H.BENCH / "peaks.json")["devices"]["TPU v5 lite"]


def ev(name, start, end):
    return Event(f"%{name} = f32[] op()", start, end)


def hand_trace():
    # window [0, 10]: a while op spans [1, 9]; inside it a fusion [1, 4],
    # two overlapping fusions [4, 6] and [5, 7], an all-gather in flight
    # [7, 8.5]; idle [0, 1] and [9, 10]
    ops = [ev("while.1", 1, 9), ev("fusion.2", 1, 4),
           ev("fusion.3", 4, 6), ev("fusion.4", 5, 7),
           ev("all-gather-start.5", 7, 7.1), ev("flash_attention.6", 7.1, 9)]
    async_ops = [ev("all-gather-start.5", 7, 8.5)]
    host = [Event("bench/epoch", 0, 10), Event("bench/dispatch", 0, 1.5)]
    return TraceData(ops=[ops], host=host, t0=0.0, t1=10.0,
                     async_ops=[async_ops])


def mamba_cell():
    """The Mamba2 configuration under the uniform mix (its cell is out of
    BENCHMARK.json while the program's SSD backward is at fault)."""
    cell = H.find_cell("internlm2-1.8b.hard1of16")
    cell.config = H.load_json(H.BENCH / "configs" / "mamba2-2.7b.json")
    cell.traffic = H.load_json(H.BENCH / "traffic" / "uniform.json")
    return cell


def context(t, cell=None, steps=16, trips=3):
    cell = cell or H.find_cell("internlm2-1.8b.hard1of16")
    return Context(cell=cell, peaks=PEAKS, trace=t, steps=steps, trips=trips)


def test_busy_idle_and_gaps_take_unions():
    t = hand_trace()
    assert t.busy(0) == pytest.approx(8.0)
    assert H.metric_reader("device.idle_share")(context(t)) == pytest.approx(20.0)
    assert t.gaps(0) == [(0.0, 1.0), (9.0, 10.0)]
    b = TR.breakdown(t)
    assert [g[0] for g in b["idle_gaps"]] == ["dispatch", "metric fetch / host loop"]
    assert "while" not in [k for k, _ in b["device_ops"]]


def test_overlapping_ops_count_once():
    t = hand_trace()
    assert t.time(t.ops[0][2:4]) == pytest.approx(3.0)


def test_collective_share_uses_the_in_flight_span():
    read = H.metric_reader("reduction.collective_share")
    assert read(context(hand_trace())) == pytest.approx(15.0)


def test_mfu_counts_subproblem_trips_as_passes():
    read = H.metric_reader("mfu")
    t = hand_trace()
    assert read(context(t, steps=16, trips=3)) == pytest.approx(
        read(context(t, steps=16, trips=0)) * 19 / 16)


def test_kernel_roofline_finds_its_calls_by_instruction_name():
    t = hand_trace()
    calls = TR.kernel_calls(t, r"^flash_attention\b")
    assert [e.op for e in calls] == ["flash_attention.6"]
    assert TR.kernel_calls(t, r"^ssd_intra_chunk\b") == []
    assert H.metric_reader("ssd_scan_roofline")(
        context(t, mamba_cell())) is None


RECORDED = sorted(p.name.split(".xplane")[0]
                  for p in DATA.glob("*.xplane.pb.gz"))


def load_recorded(name):
    d = tempfile.mkdtemp()
    try:
        with gzip.open(DATA / f"{name}.xplane.pb.gz") as f, \
                open(Path(d) / "t.xplane.pb", "wb") as g:
            shutil.copyfileobj(f, g)
        return TR.load(d, 1)
    finally:
        shutil.rmtree(d)


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_chip_trace(name):
    """Readers on a trace recorded on the chip (``<cell>.json`` says which
    run): every share within [0, 100], one kernel call per layer per
    forward (remat makes a second) and one fused xent per pass, and the
    values the readers gave when the fixture was recorded."""
    rec = json.loads((DATA / f"{name}.json").read_text())
    t = load_recorded(name)
    cell = H.find_cell(rec["cell"])
    ctx = Context(cell=cell, peaks=PEAKS, trace=t, steps=rec["steps"],
                  trips=rec["trips"])
    assert t.busy_s() == pytest.approx(rec["busy_s"])
    assert t.window_s == pytest.approx(rec["window_s"])
    for m in cell.per_layer:
        v = H.metric_reader(m["name"])(ctx)
        assert v == pytest.approx(rec["metrics"].get(m["name"]))
        if v is not None and m["unit"] == "%":
            assert 0.0 <= v <= 100.0, (m["name"], v)
    passes = rec["steps"] + rec["trips"]
    layers = cell.config["model"]["num_layers"]
    assert len(TR.kernel_calls(t, r"fused_xent")) == passes
    assert len(TR.kernel_calls(t, r"^flash_attention\b")) == 2 * layers * passes
