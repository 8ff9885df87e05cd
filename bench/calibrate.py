"""Readings that the limits of a cell's comparison are set from, in one
process on the cell's chips: the program's numbers over many seeds, and
over a few, those of the control (the reference in fp8 in the program's
place), of the half-batch fault (the reference on half of each batch) and,
on several chips, of the exchange left out (the reference on the first
chip's rows alone, which is what the replicated update keeps when the
gradients are not reduced).

    python3 bench/calibrate.py <cell> --seeds 101-112 --controls 3

Prints one JSON line per seed; ``bench/limits/<cell>.json`` records the
limits set from them.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def seed_list(spec: str):
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)

    from bench import compare, harness as H
    H.setup_cache()
    cell = H.find_cell(args.cell)
    devices, _ = H.check_devices(cell, True)
    prog_ = H.Program(cell, devices, "pallas")
    quiet = lambda *a: None   # noqa: E731
    for i, seed in enumerate(seed_list(args.seeds)):
        t = time.perf_counter()
        call, state, ring, epoch, _ = prog_.start(seed)
        with prog_.mesh:
            state, _, _, prog = prog_.first_chunk(seed, call, state, ring)
            del state, call, ring
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
        rec["seconds"] = time.perf_counter() - t
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
