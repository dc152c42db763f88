"""Trace one cell's window and print the program's span and scope tables.

    python3 bench/spanreport.py --workload <cell> --seed <n>

Run from the root of a checkout, on the chips the cell asks for. Set-up is
the benchmark's own (``harness.build`` and ``harness.Driver``: rounds until
the tier assignment repeats); the profiler then records ``TRACE_ROUNDS``
window rounds. Standard error gets the tables of ``spans.table`` as
``[bench] span ...`` and ``[bench] scope ...`` lines; the last line on
standard output is one JSON object of the window's rounds and seconds and
the readings of ``spans.readings`` (null where the trace gives nothing to
read). It makes no comparison with the reference and measures nothing end
to end: a traced window is slower than an untraced one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import cells  # noqa: E402
import spans  # noqa: E402


def report(cell, seed: int, require_tpu: bool = True) -> dict:
    """Set up ``cell``, trace its window and return the readings (printing
    the tables). ``require_tpu=False`` lets a test run it on the CPU."""
    import jax

    import harness

    class Driver(harness.Driver):
        """The benchmark's driver; its trace is read with each operation's
        scope (``spans.load``) before it is removed."""

        def close_trace(self) -> dict:
            while self._spans:
                self._spans.pop().__exit__(None, None, None)
            jax.profiler.stop_trace()
            files = glob.glob(str(harness.TRACE_DIR / "**" / "*.xplane.pb"), recursive=True)
            rec = spans.load(files[0]) if files else {}
            shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
            return rec

    if require_tpu:
        harness.setup_cache()
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise SystemExit(f"this cell needs {cell.chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    fed, _ = harness.build(cell, seed)
    drv = Driver(fed, float("inf"), harness.CompileLog(), trace=True)
    try:
        fed.run(on_round=drv.on_round)
    except harness.WindowClosed:
        pass
    else:
        raise RuntimeError("Federation.run ended before the window closed")
    harness.say(f"set-up {drv.t0 - T_START:.3f} s: {len(drv.logs)} rounds")
    red = spans.reduce(drv.close_trace())
    for line in spans.table(red):
        harness.say(line)
    h2d = [log.h2d_bytes for log in drv.window_logs]
    return {"rounds": len(h2d), "window_s": drv.t1 - drv.t0,
            "readings": spans.readings(red, len(h2d), sum(h2d))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    cell = cells.load(args.workload)
    sys.path.insert(0, str(cells.ROOT / "src"))
    print(json.dumps(report(cell, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
