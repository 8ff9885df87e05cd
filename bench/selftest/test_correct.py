"""``correct`` comes out false when the timed path is broken underneath,
and true when it is not: the harness's run at a size a test run can hold,
past its look for a chip, against the cell's limits set at that size by
the rule of its chip limits (``bench/limits/<cell>.tiny.json``).  The
control (the reference in fp8 in the program's place) fails the same
limits."""
import time

import numpy as np
import pytest

from bench import calibrate, compare
from bench import harness as H
from bench.selftest.tiny import FIRING, tiny_cell

CELLS = [w["name"] for w in H.load_json(H.ROOT / "BENCHMARK.json")["workloads"]]


def limits_of(name):
    """The cell's limits at the tiny size, set by the rule of its chip
    limits from tiny readings (``bench/calibrate.py --tiny``)."""
    return H.load_json(H.BENCH / "limits" / f"{name}.tiny.json")["limits"]


def run(name, loss_wrap=None, monkeypatch=None, patch=None, isgd=None):
    """The harness's run of the tiny cell ``name``; ``isgd`` overrides the
    configuration's ISGD settings."""
    cell = tiny_cell(name, chips=H.find_cell(name).chips,
                     limits=limits_of(name))
    cell.config["isgd"].update(isgd or {})
    if patch is not None:
        patch(monkeypatch)
    res = H.run(cell, 2**31 + 7, 0.5, False, t_start=time.perf_counter(),
                require_chip=False, kernels="reference", loss_wrap=loss_wrap,
                log=lambda *a: None)
    return res


def state_unchanged(loss_fn):
    import jax
    return lambda p, b: jax.tree.map(jax.lax.stop_gradient, loss_fn(p, b))


def half_batch(loss_fn):
    return lambda p, b: loss_fn(p, {k: v[:v.shape[0] // 2]
                                    for k, v in b.items()})


def loss_altered(loss_fn):
    def f(p, b):
        total, data = loss_fn(p, b)
        return total * 1.01, data * 1.01
    return f


def subproblem_unchanged(monkeypatch):
    calibrate.subproblem_unchanged(monkeypatch.setattr)


def no_exchange(monkeypatch):
    from repro.core.reduce import LOCAL
    from repro.distributed import data_parallel
    monkeypatch.setattr(data_parallel, "AxisReduce",
                        lambda *a, **k: LOCAL)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run(name)
    assert res["correct"], res["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_tokens_per_s_counts_subproblem_trips(name):
    """Each trip counts as one pass over the global batch, as in ``mfu``, and
    the subproblem is compared with the reference's."""
    res = run(name, isgd=FIRING)
    assert res["trips"] > 0
    assert res["correct"], res["compared"]
    assert "trip_loss_gap" in res["compared"]
    cell = tiny_cell(name, chips=H.find_cell(name).chips)
    assert res["metrics"]["tokens_per_s"]["value"] == pytest.approx(
        (res["attempted"] + res["trips"]) * cell.tokens_per_step
        / res["window_s"], rel=1e-12)


@pytest.mark.parametrize("name", CELLS)
def test_subproblem_unchanged_is_not_correct(name, monkeypatch):
    res = run(name, monkeypatch=monkeypatch, patch=subproblem_unchanged,
              isgd=FIRING)
    assert res["trips"] > 0
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [state_unchanged, half_batch, loss_altered])
def test_fault_is_not_correct(name, fault):
    res = run(name, loss_wrap=fault)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", [c for c in CELLS
                                  if H.find_cell(c).chips > 1])
def test_no_exchange_is_not_correct(name, monkeypatch):
    res = run(name, monkeypatch=monkeypatch, patch=no_exchange)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny_cell(name, chips=1, limits=limits_of(name))
    from bench.traffic.gen import make_epoch
    seed = 2**31 + 11
    epoch, _ = make_epoch(cell.traffic, seed, cell.config["model"]["vocab_size"],
                          cell.chips)
    ref = compare.reference_run(cell, seed, epoch)
    ctl = compare.reference_run(cell, seed, epoch, quant="fp8")
    gaps = compare.gaps(ctl, ref, log=lambda *a: None)
    lim = limits_of(name)
    assert any(gaps[k] > lim[k] for k in gaps), gaps
    assert np.isfinite(list(gaps.values())).all()
