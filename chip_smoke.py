"""Chip smoke test: a DTFL ResNet-110 federation end to end on a TPU.

    python chip_smoke.py               # one chip: 3 DTFL rounds + loop check
    python chip_smoke.py --four-chips  # four chips: sharded plane vs cohort

The one-chip run builds ResNet-110 at its published width (16 stem
channels, 32x32x3 inputs, 8 modules = 7 tiers) through the normal
``ExperimentSpec -> Federation.run()`` path, on seeded synthetic CIFAR-10
data, and trains 3 rounds of DTFL with the dynamic tier scheduler on the
cohort plane. Per round it prints the wall time (after the round's device
work has finished), the tier assignment, the eval loss and accuracy, and the
compiles the round triggered. It then checks the chip's numbers: from the
same seed and with the same tier assignment, one round of the per-client
``loop`` plane must update the parameters as one round of the cohort plane
does, both at HIGHEST matmul precision with the convolutions as im2col (see
``FUNC_REL_TOL``).

``--four-chips`` runs only the sharded plane (client axis over a 4-chip
mesh) against the cohort plane on the same spec and seed, both at HIGHEST
precision with the convolutions as im2col, compares their parameters, simulated clocks and tiers, and
reports how many clients each chip trained.

The script refuses to run without a TPU (no CPU fallback) and ends with one
JSON line ``{"ok": true, "device": {...}}``; any failed phase exits non-zero
before that line. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path
from unittest import mock

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

ROOT = Path(__file__).resolve().parent

ROUNDS = 3
CLIENTS = 4
BATCH = 32
SAMPLES = 512      # Dirichlet(0.5) split over 4 clients: 7/2/1/4 batches
SEED = 0

# Plane check: two planes run round 0 from the same parameters and aux
# heads, at HIGHEST matmul precision (f32 through six bf16 MXU passes),
# so that only accumulation order differs between them (vmapped vs
# per-client GEMMs, a psum over chips). The check compares what the two
# trained models compute: with f(.) the logits on the eval batch and f0
# those of the initial state, it holds
# ||f_a - f_b|| / ||f_b - f0|| to FUNC_REL_TOL.
#
# Why logits and not parameters: Adam's first steps move each weight by
# about lr * sign(grad), so wherever a gradient is near zero, reordering
# noise flips the step, and over 110 layers and several steps the parameter
# updates drift apart along directions the loss hardly sees. Measured
# relative parameter-update differences between correct planes: 1.3e-2 on
# XLA:CPU in f32 (4 clients, batch 8, up to 4 steps), 9.1e-2 on a v5e at
# HIGHEST and 2.7e-1 at DEFAULT precision (this configuration). The logits
# of the same CPU pair differ by 5.0e-4, while training the cohort plane on
# the wrong clients' batches moves them by 6.3e-1. The parameter difference
# is printed, not held to a bound.
#
# The check trains one round per plane: every new cohort shape costs a
# compile of about two minutes at HIGHEST precision, and round 0 already
# puts all 4 clients in one cohort (one client per chip on 4 chips).
#
# The checks run with the ResNet's convolutions as im2col
# (``resnet.conv_im2col``), not as the conv primitive the chip's default
# programs use: at HIGHEST the v5e compiler spends minutes on each conv
# primitive's weight gradient (a ResNet-110 forward and backward compiled
# in 850 s, against 71 s as im2col). So these checks do not cover the conv
# primitive; tests/test_native_conv_planes.py holds the planes to each
# other under it, on the CPU.
FUNC_REL_TOL = 5e-2

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@contextlib.contextmanager
def highest_with_im2col():
    """HIGHEST matmul precision, with every conv of the ResNet as im2col."""
    from repro.models import resnet

    with jax.default_matmul_precision("highest"), \
            mock.patch.object(resnet, "conv", resnet.conv_im2col):
        yield


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileCounter:
    """Executables built (``backend``) and how many of them the persistent
    cache supplied (``hits``); the rest were compiled in this process."""

    def __init__(self):
        self.backend = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        self.backend += event == COMPILE_EVENT

    def _on_event(self, event, **kw):
        self.hits += event == CACHE_HIT_EVENT

    def snapshot(self) -> tuple[int, int]:
        return self.backend, self.hits


def make_spec(exec_mode: str, rounds: int, *, devices: int | None = None):
    from repro.api import (DataSpec, ExecSpec, ExperimentSpec, ModelSpec,
                           TrainerSpec)

    return ExperimentSpec(
        model=ModelSpec(arch="resnet-110", full_size=True),
        data=DataSpec(dataset="cifar10", clients=CLIENTS, samples=SAMPLES,
                      batch_size=BATCH),
        trainer=TrainerSpec(method="dtfl", scheduler="dynamic"),
        exec=ExecSpec(mode=exec_mode, devices=devices),
        rounds=rounds, seed=SEED)


def host_tree(tree):

    return jax.tree.map(np.asarray, tree)


def check_finite(name: str, tree) -> None:

    bad = [i for i, x in enumerate(jax.tree.leaves(tree))
           if not np.isfinite(np.asarray(x)).all()]
    if bad:
        fail(f"{name}: {len(bad)} leaves hold non-finite values")


def update_rel_diff(start, a, b) -> tuple[float, float]:
    """(||d_a - d_b|| / ||d_b||, max |a - b|) with d = state - start."""

    num = den = 0.0
    max_abs = 0.0
    for x0, xa, xb in zip(*(jax.tree.leaves(t) for t in (start, a, b))):
        x0, xa, xb = (np.asarray(t, np.float64) for t in (x0, xa, xb))
        num += float(((xa - xb) ** 2).sum())
        den += float(((xb - x0) ** 2).sum())
        max_abs = max(max_abs, float(np.abs(xa - xb).max()))
    if den == 0.0:
        fail("the reference plane did not change the parameters")
    return (num / den) ** 0.5, max_abs


def check_round0(label: str, ref_label: str, fed, init, state,
                 ref_state) -> None:
    """Hold round 0 of ``state`` to that of ``ref_state`` (FUNC_REL_TOL)."""
    from repro.models import resnet

    images = jax.numpy.asarray(fed.eval_batch["images"])
    with highest_with_im2col():
        fwd = jax.jit(lambda p: resnet.forward(p, fed.cfg, images))

        def logits(st):
            params = jax.tree.map(jax.numpy.asarray, st[0])
            return np.asarray(fwd(params), np.float64)

        f0, fa, fb = logits(init), logits(state), logits(ref_state)
    rel = float(np.linalg.norm(fa - fb) / np.linalg.norm(fb - f0))
    p_rel, p_max = update_rel_diff(init, state, ref_state)
    say(f"check {label}-vs-{ref_label} round 0: logits_rel_diff={rel:.3e} "
        f"(tol {FUNC_REL_TOL:g}) param_update_rel_diff={p_rel:.3e} "
        f"max_abs_param_diff={p_max:.3e}")
    if not rel <= FUNC_REL_TOL:
        fail(f"{label} and {ref_label} planes disagree: {rel:.3e} > "
             f"{FUNC_REL_TOL:g}")


def run_federation(label: str, spec, counter):
    """Build and run ``spec``, printing one line per round. Returns the
    Federation, its initial (params, aux heads) and those after each round
    (host copies)."""

    fed = spec.build()
    tr = fed.trainer
    init = host_tree((tr.params, tr.aux))
    loss_fn = jax.jit(fed.adapter.full_loss)
    eval_batch = {k: jax.numpy.asarray(v) for k, v in fed.eval_batch.items()}
    states = []
    mark = {"t": time.perf_counter(), "c": counter.snapshot()}

    def on_round(log):
        jax.block_until_ready(tr.params)
        wall = time.perf_counter() - mark["t"]
        (b0, h0), (b1, h1) = mark["c"], counter.snapshot()
        loss = float(loss_fn(tr.params, eval_batch))
        state = host_tree((tr.params, tr.aux))
        check_finite(f"{label} round {log.round} state", state)
        if not (np.isfinite(loss) and np.isfinite(log.acc)):
            fail(f"{label} round {log.round}: loss={loss} acc={log.acc}")
        tiers = {t: sorted(k for k, v in log.assignment.items() if v == t)
                 for t in sorted(set(log.assignment.values()))}
        say(f"{label} round={log.round} wall_s={wall:.3f} tiers={tiers} "
            f"eval_loss={loss:.4f} eval_acc={log.acc:.4f} "
            f"compiles={(b1 - b0) - (h1 - h0)} cache_hits={h1 - h0} "
            f"sim_clock_s={log.clock:.1f}")
        states.append(state)
        # the eval loss's own compile and run belong to no round
        mark["t"], mark["c"] = time.perf_counter(), counter.snapshot()

    logs = fed.run(on_round=on_round)
    if len(logs) != spec.rounds:
        fail(f"{label}: {len(logs)} rounds logged, {spec.rounds} asked for")
    return fed, init, states


def one_chip(counter) -> None:
    run_federation("cohort", make_spec("cohort", ROUNDS), counter)
    say("check: one round of the loop and cohort planes at HIGHEST precision, "
        "convs as im2col")
    with highest_with_im2col():
        coh_fed, init, coh_states = run_federation(
            "cohort@highest", make_spec("cohort", 1), counter)
        loop_fed, loop_init, loop_states = run_federation(
            "loop@highest", make_spec("loop", 1), counter)
    check_same_init(init, loop_init)
    a0, b0 = coh_fed.logs[0].assignment, loop_fed.logs[0].assignment
    if a0 != b0:
        fail(f"loop round 0 tiers {b0} != cohort round 0 tiers {a0}")
    check_round0("loop", "cohort", coh_fed, init, loop_states[0], coh_states[0])


def check_same_init(a, b) -> None:

    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if not np.array_equal(x, y):
            fail("the two federations did not start from the same parameters")


def four_chips(counter) -> None:
    from repro.fed import cohort as cohort_engine

    if len(jax.devices()) < 4:
        fail(f"--four-chips needs 4 chips, JAX sees {len(jax.devices())}")
    say("sharded and cohort planes at HIGHEST precision, convs as im2col")
    with highest_with_im2col():
        coh_fed, init, coh_states = run_federation(
            "cohort@highest", make_spec("cohort", 1), counter)
        sh_fed, sh_init, sh_states = run_federation(
            "sharded@highest", make_spec("sharded", 1, devices=4), counter)
    check_same_init(init, sh_init)

    plan = sh_fed.trainer.exec_plan
    mesh_devs = [d.id for d in plan.mesh.devices.flat]
    say(f"mesh {plan.describe()} devices={mesh_devs}")
    if len(set(mesh_devs)) != 4:
        fail(f"the client mesh does not span 4 distinct chips: {mesh_devs}")
    per_device = dict.fromkeys(mesh_devs, 0)
    by_cols = NamedSharding(plan.mesh, P(None, plan.axis))
    log = sh_fed.logs[0]
    cohorts = cohort_engine.build_cohorts(
        sh_fed.clients, sorted(log.assignment), log.assignment, log.round,
        sh_fed.trainer.local_epochs, pad_multiple=plan.pad_multiple)
    for co in cohorts:
        # the cohort program's client axis is split as shard_map's in_specs
        # split it: P(None, axis) over the mesh
        placed = jax.device_put(co.mask, by_cols)
        where = {}
        for shard in placed.addressable_shards:
            cols = range(co.mask.shape[1])[shard.index[1]]
            where[shard.device.id] = [co.cids[c] for c in cols if c < co.size]
            per_device[shard.device.id] += len(where[shard.device.id])
        say(f"round=0 tier={co.tier} width={co.size}+{co.n_pad}pad "
            f"clients_by_device={where}")
    if min(per_device.values()) == 0:
        fail(f"some chip trained no client: {per_device}")

    a, b = coh_fed.logs[0], sh_fed.logs[0]
    if a.assignment != b.assignment or a.clock != b.clock:
        fail(f"sharded tiers/clock {b.assignment}/{b.clock} != cohort "
             f"{a.assignment}/{a.clock}")
    say("check sharded-vs-cohort RoundLog: tiers and simulated clock identical")
    check_round0("sharded", "cohort", coh_fed, init, sh_states[0],
                 coh_states[0])


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded plane on a 4-chip mesh "
                         "against the cohort plane")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro sources under {ROOT / 'src'}; run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r}); this smoke "
             "test runs on the chip only")
    from repro.launch.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    counter = CompileCounter()
    say(f"device_kind={dev.device_kind} device_count={len(jax.devices())} "
        f"jax={jax.__version__} compile_cache={cache_dir}")

    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(counter)
    b, h = counter.snapshot()
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in jax.devices()]
    say(f"total_s={time.perf_counter() - t0:.1f} executables={b} "
        f"compiled={b - h} cache_hits={h} peak_bytes_in_use={peaks}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
