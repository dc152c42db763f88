"""The control, the reference computed in bfloat16 where the configuration
states float32, fails a cell's limits (at the reduced model's size)."""
import pytest

import tinycell

import control


@pytest.mark.parametrize("traffic", ["cohort.iid"])
def test_bfloat16_control_is_not_correct(traffic):
    cell = tinycell.tiny(traffic)
    lines = {l["reading"]: l for l in control.readings(cell, [5], 1, faults=("control",))}
    over = {k: lines["control"][k] for k, lim in cell.limits.items()
            if not lines["control"][k] <= lim}
    assert over, (lines["control"], cell.limits)
