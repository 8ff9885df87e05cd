"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and, last, ``compared``: each number of the
comparison with the plain reference beside its limit.  ``tokens_per_s`` is
the tokens through the ISGD step's forward+backward passes per second: each
main step and each subproblem trip on its batch; tracing off.  The same
numbers are the last lines of standard error.  With no TPU, fewer chips
than the cell asks for, or a device kind missing from ``bench/peaks.json``,
it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# libtpu otherwise logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(*args):
    print(*args, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro
        from bench import harness as H
    except Exception:
        traceback.print_exc()
        return 1
    where = Path(next(iter(repro.__path__))).resolve()
    if not where.is_relative_to(ROOT / "src"):
        print(f"bench: repro imported from {where}, not from this "
              f"checkout's src/", file=sys.stderr)
        return 1
    try:
        cell = H.find_cell(args.workload)
        res = H.run(cell, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START, log=log)
    except H.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    compared = res.pop("compared")
    for name, v in compared.items():
        print(f"{name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr,
              flush=True)
    res["compared"] = compared
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
