"""Record the chip trace a reader self-test reads: one traced run of a cell
(``bench/run.py --trace 1``), kept as ``bench/selftest/data/<cell>.xplane.pb.gz``
with ``<cell>.json`` (the run, its counters, and the values the readers
gave).

    python3 bench/selftest/record_trace.py <cell> <seed> <out dir>
"""
import gzip
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(name, seed, out):
    from bench import harness as H
    out = Path(out)
    out.mkdir(parents=True, exist_ok=True)
    keep = tempfile.mkdtemp()
    try:
        res = H.run(H.find_cell(name), seed, 1.0, True,
                    t_start=time.perf_counter(),
                    keep_trace=os.path.join(keep, "t.xplane.pb"))
        with open(os.path.join(keep, "t.xplane.pb"), "rb") as f, \
                gzip.open(out / f"{name}.xplane.pb.gz", "wb") as g:
            shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(keep)
    rec = {"cell": name, "seed": seed, "steps": res["attempted"],
           "trips": res["trips"],
           "source": f"bench/run.py --trace 1 of this cell (seed {seed}) on "
                     f"{res['device']['kind']}",
           "busy_s": res["device"]["busy_s"],
           "window_s": res["device"]["window_s"],
           "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
    (out / f"{name}.json").write_text(json.dumps(rec, indent=1))
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), sys.argv[3])
