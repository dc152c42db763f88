"""The round's instrumentation: program names and scopes inside the per-tier
programs, the per-round host-to-device byte counter, and the host spans a
profiler trace holds.

The 4-device sharded case runs in a process of its own, since host devices
are fixed when JAX starts.
"""
import glob
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import optim
from repro.configs.resnet_cifar import RESNET56
from repro.data.partition import iid_partition
from repro.data.pipeline import ClientDataset, make_eval_batch, materialize_round
from repro.data.synthetic import ClassImageTask
from repro.fed import (DTFLTrainer, ExecPlan, FedAvgTrainer, HeteroEnv,
                       ResNetAdapter, SimClient)
from repro.fed import cohort as cohort_engine
from repro.launch.mesh import make_sim_mesh

SRC = Path(__file__).resolve().parents[1] / "src"
TIER = 1     # one static tier: every plane trains the 4 clients as one cohort


def build(n_clients=4, samples=64, batch=16):
    cfg = RESNET56.reduced()
    task = ClassImageTask(n_classes=10, image_size=cfg.image_size)
    labels = np.random.default_rng(0).integers(0, 10, samples)
    parts = iid_partition(labels, n_clients, 0)
    clients = [SimClient(i, ClientDataset(task, labels, parts[i], batch), None)
               for i in range(n_clients)]
    return ResNetAdapter(cfg, cost_cfg=RESNET56), clients, make_eval_batch(task, 32)


def trainer(cls, adapter, clients, exec_plan, **kw):
    return cls(adapter, clients, HeteroEnv(len(clients), seed=0),
               optim.adam(1e-3), seed=0, exec_plan=exec_plan, **kw)


def round_input_bytes(clients, r, local_epochs=1):
    """Bytes of the round's batches plus a (steps, clients) bool step mask."""
    per = [materialize_round(cl.dataset, r, local_epochs) for cl in clients]
    steps = max(len(next(iter(p.values()))) for p in per)
    return sum(a.nbytes for p in per for a in p.values()) + steps * len(per)


@pytest.mark.parametrize("tier", [0, 2])
def test_cohort_program_carries_its_name_and_scopes(tier):
    adapter, clients, _ = build()
    tr = trainer(DTFLTrainer, adapter, clients, "cohort", scheduler=tier)
    (co,) = cohort_engine.build_cohorts(
        clients, list(range(4)), {k: tier for k in range(4)}, 0, 1)
    text = tr._cohort_program(tier).lower(
        tr.params, tr.aux[tier], co.batches, co.mask).as_text(debug_info=True)
    assert f"module @jit_dtfl_cohort_t{tier} " in text
    for scope in ("dtfl.client", "dtfl.server", "dtfl.optimizer", "dtfl.state"):
        assert f"{scope})" in text or f"{scope}/" in text, scope


@pytest.mark.parametrize("cls,mode,name", [
    (DTFLTrainer, "sharded", f"jit_dtfl_sharded_t{TIER}"),
    (FedAvgTrainer, "cohort", "jit_fedavg_cohort"),
    (FedAvgTrainer, "sharded", "jit_fedavg_sharded"),
])
def test_program_names(cls, mode, name):
    adapter, clients, _ = build()
    plan = ExecPlan.sharded(make_sim_mesh(1)) if mode == "sharded" else mode
    kw = {"scheduler": TIER} if cls is DTFLTrainer else {}
    tr = trainer(cls, adapter, clients, plan, **kw)
    (co,) = cohort_engine.build_cohorts(
        clients, list(range(4)), {k: TIER for k in range(4)}, 0, 1)
    w = co.client_weights(clients)
    if cls is DTFLTrainer:
        prog, args = tr._sharded_program(TIER), (tr.params, tr.aux[TIER])
    elif mode == "sharded":
        prog, args = tr._full_sharded_program(), (tr.params,)
    else:
        tr._train_round_full(0, list(range(4)))
        prog, args = tr._full_cohort_program, (tr.params,)
    args += (co.batches, co.mask) + ((w,) if mode == "sharded" else ())
    assert f"module @{name} " in prog.lower(*args).as_text()


PLANES = {"cohort": lambda: "cohort",
          "chunked": lambda: ExecPlan.chunked(2),
          "sharded": lambda: ExecPlan.sharded(make_sim_mesh(1))}


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_h2d_bytes_counts_the_round_inputs(plane):
    """The same count under the rounds and the events engine (the second
    run reuses the first one's programs)."""
    adapter, clients, ev = build()
    tr = trainer(DTFLTrainer, adapter, clients, PLANES[plane](), scheduler=TIER)
    want = round_input_bytes(clients, 0)
    assert want > 0
    for engine in ("rounds", "events"):
        (log,) = tr.run(1, ev, engine=engine)
        assert log.h2d_bytes == want, engine


FOUR_DEVICES = """
import sys
sys.path.insert(0, %r)
from test_tracing import *
adapter, clients, ev = build()
tr = trainer(DTFLTrainer, adapter, clients, ExecPlan.sharded(make_sim_mesh(4)),
             scheduler=TIER)
print("H2D", tr.run(1, ev)[0].h2d_bytes, round_input_bytes(clients, 0))
"""


def test_h2d_bytes_on_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(SRC))
    out = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES % str(Path(__file__).parent)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    (line,) = [x for x in out.stdout.splitlines() if x.startswith("H2D")]
    _, got, want = line.split()
    assert int(got) == int(want) > 0


def test_round_spans_in_a_profiler_trace(tmp_path):
    adapter, clients, ev = build()
    tr = trainer(DTFLTrainer, adapter, clients, "cohort")
    tr.run(1, ev)                       # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        tr.run(1, ev)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                      for e in line.events if e.name.startswith("fed.")]
    (rnd,) = [s for s in spans if s[0] == "fed.round"]
    assert rnd[3]["round"] == 0
    inside = {name for name, a, b, _ in spans if rnd[1] <= a and b <= rnd[2]}
    assert {"fed.plan", "fed.assemble", "fed.dispatch", "fed.aggregate",
            "fed.eval", "fed.observe"} <= inside
    tiers = {st["tier"] for name, _, _, st in spans if name == "fed.dispatch"}
    assert tiers and tiers <= set(range(adapter.n_tiers))
