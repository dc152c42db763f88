"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--controls 3]

For each seed, in one process: set-up as a run makes it (the program's
first rounds through ``Federation.run``), then the plain reference, and the
numbers of ``check.py`` for the program against it: the lower readings.
For the first ``--controls`` seeds also, each put in the program's place
and read against the reference the same way:

- ``control``: the reference computed in bfloat16, weights and optimizer
  state too (the configuration states float32);
- ``half_batch``: the reference stepping on half of every batch, the mean
  taken over the rest;
- ``half_update``: the reference moving the model half of the way to the
  round's average;
- ``no_exchange`` (sharded cells): the reference averaging only the clients
  on the first chip, as the program would without its psums.

A state left unchanged reads 1 on the change gaps by their definition and
needs no run. Prints one JSON line per seed and reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import cells

FAULTS = ("control", "half_batch", "half_update", "no_exchange")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--controls", type=int, default=3,
                    help="how many of the seeds also read the control and faults")
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    sys.path.insert(0, str(cells.ROOT / "src"))
    import jax

    import harness

    harness.setup_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        raise SystemExit(f"needs {cell.chips} TPU chip(s); found {len(devs)} "
                         f"{devs[0].platform} device(s)")
    seeds = [int(s) for s in args.seeds.split(",")]
    for line in readings(cell, seeds, args.controls):
        print(json.dumps(line), flush=True)
    return 0


def readings(cell, seeds, n_controls: int, faults=FAULTS):
    """Yields {"seed", "reading", numbers...} for each seed and reading."""
    import harness

    compiles = harness.CompileLog()
    prev = None
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        fed, init = harness.build(cell, seed, reuse=prev)
        drv = harness.drive(fed, 0.0, compiles, False)
        inputs = harness.reference_inputs(fed, drv)
        states = drv.states
        prev = fed
        ref = harness.run_reference(cell, init, inputs)
        yield _line(cell, seed, "program", init, states, ref, inputs, t)
        if i >= n_controls:
            continue
        for fault in faults:
            if fault == "no_exchange" and inputs["pad_multiple"] == 1:
                continue
            t = time.perf_counter()
            kw = {"dtype": "bfloat16"} if fault == "control" else {"fault": fault}
            planted = harness.run_reference(cell, init, inputs, **kw)
            yield _line(cell, seed, fault, init, planted, ref, inputs, t)


def _line(cell, seed, reading, init, states, ref, inputs, t) -> dict:
    import check

    nums = check.numbers(cell.arch, init, states, ref, inputs["eval"])
    leaves = nums.pop("_leaves")
    return {"seed": seed, "reading": reading, **nums, "leaves": leaves,
            "seconds": time.perf_counter() - t}


if __name__ == "__main__":
    sys.exit(main())
