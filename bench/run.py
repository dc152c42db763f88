"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell's configuration, traffic mix and
limits are the files that ``BENCHMARK.json`` names (see ``cells.py``). The
run refuses to start without as many TPU chips as the cell asks for. Its
last line on standard output is one JSON object: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window; ``correct`` is the comparison with the
plain reference (``check.py``), whose numbers and limits come last, and are
also the last lines on standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402

import cells  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.load(args.workload)
    sys.path.insert(0, str(cells.ROOT / "src"))
    import harness

    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(harness.result_line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
