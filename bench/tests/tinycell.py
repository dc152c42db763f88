"""A cell small enough for the CPU: the program's reduced ResNet (one
bottleneck block a stage, width 8, 16x16 inputs, 4 modules = 3 tiers)
under one of the benchmark's traffic mixes, held to a cell's limits."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import cells  # noqa: E402

ARCH = {"blocks_per_stage": 1, "width": 8, "n_classes": 10, "image_size": 16,
        "n_modules": 4}
LIMITS_OF = "r110.cohort.iid4"


def tiny(traffic: str, clients: int = 4, samples: int = 16, batch: int = 8) -> cells.Cell:
    """The traffic mix ``traffic`` on the reduced model, with the limits of
    ``LIMITS_OF``."""
    real = cells.load(LIMITS_OF)
    config = copy.deepcopy(real.config)
    config.update(arch=ARCH, clients=clients, samples_per_client=samples)
    config["spec"]["model"]["full_size"] = False
    config["spec"]["data"]["batch_size"] = batch
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return cells.Cell(name=f"tiny.{traffic}", config_name="tiny", config=config,
                      traffic=mix, chips=int(mix["exec"].get("devices", 1)),
                      limits=real.limits, end_to_end=real.end_to_end,
                      per_layer=real.per_layer)


def run(cell, seed: int = 3, seconds: float = 0.2) -> dict:
    import time

    import harness

    return harness.run(cell, seed, seconds, False, time.perf_counter(),
                       require_tpu=False)
