"""Readings that the limits of a cell's comparison are set from, in one
process on the cell's chips: the program's numbers over many seeds, and
over a few, those of the control (the reference in fp8 in the program's
place), of the half-batch fault (the reference on half of each batch) and,
on several chips, of the exchange left out (the reference on the first
chip's rows alone, which is what the replicated update keeps when the
gradients are not reduced).  The subproblem's numbers come from the chunk
that holds the first accelerated step after the mix's warm-up, as in a run
whose window starts there; ``--fault subproblem_unchanged`` plants trips
that leave the weights as they found them, and its readings are the
program's.

    python3 bench/calibrate.py <cell> --seeds 101-112 --controls 3
    JAX_PLATFORMS=cpu python3 bench/calibrate.py <cell> --tiny --seeds 1-16

Prints one JSON line per seed; ``bench/limits/<cell>.json`` records the
limits set from them, ``bench/limits/<cell>.tiny.json`` those of the
self-tests' tiny cell.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def seed_list(spec: str):
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


#: epochs of steps in which to look for the first firing after the warm-up
FIND_EPOCHS = 8


def subproblem_unchanged(set_attr=setattr):
    """Plant Alg.2 with a step of zero: each trip computes its loss and
    gradient and leaves the weights as it found them.  ``set_attr`` is
    ``setattr`` or a test's ``monkeypatch.setattr``."""
    import dataclasses

    from repro.core import isgd
    solve = isgd.solve_subproblem
    set_attr(isgd, "solve_subproblem", lambda *a: solve(
        *a[:-1], dataclasses.replace(a[-1], zeta=0.0)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--fault", choices=["subproblem_unchanged"])
    ap.add_argument("--tiny", action="store_true",
                    help="the self-tests' tiny cell on the CPU, its "
                         "controller set to fire")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.train import _drive_chunks

    from bench import compare, harness as H
    H.setup_cache()
    if args.tiny:
        from bench.selftest.tiny import FIRING, tiny_cell
        cell = tiny_cell(args.cell, chips=H.find_cell(args.cell).chips)
        cell.config["isgd"].update(FIRING)
        devices, kernels = jax.devices()[:cell.chips], "reference"
    else:
        cell = H.find_cell(args.cell)
        (devices, _), kernels = H.check_devices(cell, True), "pallas"
    if args.fault:
        subproblem_unchanged()
    prog_ = H.Program(cell, devices, kernels)
    quiet = lambda *a: None   # noqa: E731
    tr = cell.traffic
    n_b, k = tr["n_batches"], tr["chunk_steps"]
    w_start = n_b * max(1, tr["warmup_epochs"])
    stop = cell.config["isgd"]["stop"]
    for i, seed in enumerate(seed_list(args.seeds)):
        t = time.perf_counter()
        call, state, ring, epoch, _ = prog_.start(seed)
        with prog_.mesh:
            state, j, chunks, prog = prog_.first_chunk(seed, call, state, ring)
            while (compare.first_firing(H.series_of(chunks), w_start) is None
                   and j < n_b * FIND_EPOCHS):
                state, j, more = _drive_chunks(call, state, call.params, ring,
                                               j + k, k, start=j)
                chunks += more
            del state, call, ring
        series = H.series_of(chunks)
        ref = compare.reference_run(cell, seed, epoch)
        rec = {"seed": seed, "program": compare.gaps(prog, ref, log=quiet)}
        if i < args.controls:
            others = [("control_fp8", {"quant": "fp8"}),
                      ("fault_half_batch", {"part": 2})]
            if cell.chips > 1:
                others.append(("fault_no_exchange", {"part": cell.chips}))
            for what, kw in others:
                other = compare.reference_run(cell, seed, epoch, **kw)
                rec[what] = compare.gaps(other, ref, log=quiet)
        fire = compare.first_firing(series, w_start)
        rec["fired"] = np.flatnonzero(series["accelerated"]).tolist()
        if fire is not None:
            start = fire - fire % k
            host, update, replay = prog_.trip_chunk(seed, start)
            rec["replay_drift"] = float(np.max(np.abs(
                H.series_of(replay)["loss"] - series["loss"][:start + k])))
            followed = compare.reference_follow(cell, epoch, series, start,
                                                *host)
            rec["moved"] = followed["moved"]
            rec["program"].update(compare.trip_numbers(
                series, update, followed, stop, log=print))
            if i < args.controls:
                ctl = compare.reference_follow(cell, epoch, series, start,
                                               *host, quant="fp8")
                as_prog = dict(series, loss=np.concatenate(
                    [series["loss"][:start], ctl["loss"]]))
                rec.setdefault("control_fp8", {}).update(compare.trip_numbers(
                    as_prog, ctl["update"], followed, stop, log=quiet))
        rec["seconds"] = time.perf_counter() - t
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
