"""The execution planes agree when the ResNet's convolutions are the conv
primitive, as they are on an accelerator.

On the CPU ``resnet.conv`` is im2col, the lowering the bit-equality tests
between planes are pinned on; on a TPU it is ``conv_native``
(``lax.conv_general_dilated``), vmapped over the cohort's clients and, on
the sharded plane, inside ``shard_map``. Here every conv is ``conv_native``
and each plane is held to the cohort plane: the DTFL trainer's parameters
and aux heads to the plane tolerance of ``tests/test_sharded.py``, and the
full-model baseline (FedAvg, through ``fed/base.py``) by what the trained
model computes, as ``chip_smoke.py`` holds its planes, since Adam's first
steps turn reduction-order noise on near-zero gradients into parameter
differences the loss hardly sees.

The 4-device sharded case runs in a process of its own, since host devices
are fixed when JAX starts.
"""
import functools
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.fed import DTFLTrainer, ExecPlan, FedAvgTrainer
from repro.launch.mesh import make_sim_mesh
from repro.models import resnet as R
from test_sharded import build_clients, leaves_close, make_trainer

SRC = Path(__file__).resolve().parents[1] / "src"
SIZES = [64, 64, 48, 32, 16]   # ragged batch counts: chunks and the 4-mesh pad
FUNC_REL_TOL = 5e-3            # XLA:CPU reads 1.4e-5 (DTFL) to 2.6e-4 (FedAvg loop)
PLANES = {
    "loop": ExecPlan.loop,
    "chunked": lambda: ExecPlan.chunked(3),
    "sharded1": lambda: ExecPlan.sharded(make_sim_mesh(1)),
}


def native_conv(x, w, stride=1):
    native_conv.traced += 1
    return R.conv_native(x, w, stride)


native_conv.traced = 0


@pytest.fixture(scope="module", autouse=True)
def convs_are_native():
    # the trainers jit their programs per instance, so no program traced
    # here outlives the module
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(R, "conv", native_conv)
        yield


def train(cls, plan, rounds=2):
    adapter, clients = build_clients(SIZES)
    tr = make_trainer(adapter, clients, plan, cls=cls)
    init = tr.params
    before = native_conv.traced
    out = [tr.train_round(r, list(range(len(clients)))) for r in range(rounds)]
    assert native_conv.traced > before, "the programs did not trace conv_native"
    assignments = [o[1] for o in out] if cls is DTFLTrainer else None
    return tr, init, assignments


@functools.cache
def cohort(cls):
    """The cohort plane's run, the reference of every case."""
    return train(cls, ExecPlan.cohort())


def functional_gap(tr_a, tr_b, init) -> float:
    """||f_a - f_b|| / ||f_b - f0|| on an evaluation batch, with f the
    logits of the trained (a, b) and initial (0) parameters."""
    from repro.data.pipeline import make_eval_batch
    from repro.data.synthetic import ClassImageTask

    cfg = tr_b.adapter.cfg
    images = make_eval_batch(ClassImageTask(n_classes=10, image_size=cfg.image_size), 64)["images"]
    with jax.default_matmul_precision("highest"):
        f = jax.jit(lambda p: R.forward(p, cfg, images))
        fa, fb, f0 = (np.asarray(f(p), np.float64) for p in (tr_a.params, tr_b.params, init))
    return float(np.linalg.norm(fa - fb) / np.linalg.norm(fb - f0))


def check_dtfl(plan):
    coh, _, tiers_coh = cohort(DTFLTrainer)
    other, _, tiers = train(DTFLTrainer, plan)
    assert tiers == tiers_coh
    leaves_close(other.params, coh.params)
    for m in coh.aux:
        leaves_close(other.aux[m], coh.aux[m])


def check_baseline(plan):
    coh, init, _ = cohort(FedAvgTrainer)
    other, _, _ = train(FedAvgTrainer, plan)
    gap = functional_gap(other, coh, init)
    assert gap <= FUNC_REL_TOL, gap


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_dtfl_plane_matches_cohort_with_native_conv(plane):
    check_dtfl(PLANES[plane]())


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_baseline_plane_matches_cohort_with_native_conv(plane):
    check_baseline(PLANES[plane]())


FOUR_DEVICES = """
import sys
sys.path.insert(0, %r)
import test_native_conv_planes as T
from repro.models import resnet as R
R.conv = T.native_conv
plan = lambda: T.ExecPlan.sharded(T.make_sim_mesh(4))
T.check_dtfl(plan())
T.check_baseline(plan())
print("OK")
"""


def test_sharded_4dev_matches_cohort_with_native_conv():
    """shard_map + vmap + the conv primitive over 4 host devices: the
    cross-shard psum reorders the sums, and the planes still agree."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(SRC))
    out = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES % str(Path(__file__).parent)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split()[-1] == "OK"
