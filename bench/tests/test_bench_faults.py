"""A run with the timed path broken underneath comes out not correct.

Each test plants one fault in the program, drives the rest of a run of a
cell's traffic and limits on the CPU (the harness's look for a chip
skipped) at the reduced model's size, and sees ``correct`` false."""
import os
import subprocess
import sys

import pytest

import tinycell

from repro.fed import dtfl
from repro.fed.execplan import ExecPlan

COHORT = "cohort.iid"
SHARDED = "sharded.iid"


def _broken_step(fault):
    raw = dtfl.DTFLTrainer._raw_step

    def patched(self, tier):
        step = raw(self, tier)

        def run(state, batch):
            if fault == "unchanged":          # the step returns its state
                return state, step(state, batch)[1]
            # half of the batch left out, the mean taken over the rest
            return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

        return run

    return patched


def _half_update(self, r, participants, assign):
    """The round's answer altered where it is produced: the model moves half
    of the way to the average."""
    import jax

    new = _train(self, r, participants, assign)
    return jax.tree.map(lambda o, n: o + (n - o) / 2, self.params, new)


_train = dtfl.DTFLTrainer._train_participants


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(dtfl.DTFLTrainer, "_raw_step", _broken_step(fault))
    res = tinycell.run(tinycell.tiny(COHORT))
    assert res["correct"] is False, res["checks"]


def test_altered_update_is_not_correct(monkeypatch):
    monkeypatch.setattr(dtfl.DTFLTrainer, "_train_participants", _half_update)
    res = tinycell.run(tinycell.tiny(COHORT))
    assert res["correct"] is False, res["checks"]


NO_EXCHANGE = """
import jax, jax.numpy as jnp
import tinycell
from repro.fed.execplan import ExecPlan, weighted_sum

def first_chip_only(self, x):
    # each chip's sum never leaves it: every chip ends with the first's
    keep = jax.lax.axis_index(self.axis) == 0
    return jax.lax.psum(jnp.where(keep, x, jnp.zeros_like(x)), self.axis)

ExecPlan.psum_scalar = first_chip_only
ExecPlan.psum_tree = lambda self, tree, scaled_by=None: jax.tree.map(
    lambda x: first_chip_only(self, x),
    tree if scaled_by is None else weighted_sum(tree, scaled_by))
res = tinycell.run(tinycell.tiny(%r))
print("CORRECT", res["correct"], res["checks"])
"""


def test_exchange_left_out_is_not_correct():
    """The sharded plane with its psums reduced to the first chip's own sums
    (4 host devices, so in a process of its own)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(tinycell.BENCH / "tests"),
                                           str(tinycell.BENCH.parent / "src")]))
    out = subprocess.run([sys.executable, "-c", NO_EXCHANGE % SHARDED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "CORRECT False" in out.stdout, out.stdout[-2000:]


def test_sound_run_is_correct():
    """The same run with nothing broken passes the cell's limits."""
    res = tinycell.run(tinycell.tiny(COHORT))
    assert res["correct"] is True, res["checks"]
