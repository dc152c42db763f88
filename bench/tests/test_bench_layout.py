"""The benchmark's copy of the cohort rule (padding and placement on chips)
agrees with the program's ``build_cohorts``."""
import numpy as np
import pytest

import tinycell  # noqa: F401  (puts bench/ and src/ on the path)

import cells
import harness
from repro.fed.cohort import build_cohorts


class _Data:
    def __init__(self, n_batches):
        self.n = n_batches

    def epoch(self, seed):
        for _ in range(self.n):
            yield {"x": np.zeros((2, 3), np.float32)}


class _Client:
    def __init__(self, n_batches):
        self.dataset = _Data(n_batches)


@pytest.mark.parametrize("pad_multiple", [1, 4])
def test_layout_matches_build_cohorts(pad_multiple):
    assignment = {0: 6, 1: 0, 2: 6, 3: 6, 4: 0, 5: 3, 6: 6, 7: 6}
    batches = {k: 2 + k % 3 for k in assignment}
    clients = {k: _Client(n) for k, n in batches.items()}
    prog = build_cohorts(clients, sorted(assignment), assignment, 0, 1,
                         pad_multiple=pad_multiple)
    mine = harness.cohort_layout(assignment, batches, pad_multiple)
    assert [(c.tier, c.cids, c.n_pad, c.mask.shape[0]) for c in prog] == mine
    chips = harness.chip_of(assignment, pad_multiple)
    for c in prog:
        per = c.mask.shape[1] // pad_multiple
        assert all(chips[k] == i // per for i, k in enumerate(c.cids))


def test_pad_waste_reader():
    read = cells.reader("pad_waste")
    assert read({"client_steps": 40, "padded_client_steps": 10}) == 25.0
    assert read({"client_steps": 0, "padded_client_steps": 0}) is None
