"""One run of one cell: set-up, the measured window, the reading of the
trace, and the comparison with the reference.

Set-up builds the cell's ``ExperimentSpec`` into a ``Federation``, loads
into it weights that the benchmark makes from the seed, and runs rounds
through ``Federation.run`` until the tier assignment repeats (at least
three rounds, which the reference follows), so every program the window
uses is compiled or loaded from the cache. The window then runs whole
rounds in the same call until ``seconds`` have passed; each round ends with
the parameters ready on the device.
"""
from __future__ import annotations

import gc
import glob
import json
import math
import shutil
import sys
import time

import jax
import numpy as np

import cells
import check
import devtrace
import flops
import reference
from repro.api import ExperimentSpec
from repro.data.pipeline import materialize_round

CHECKED_ROUNDS = 3          # rounds of set-up that the reference follows
TRACE_ROUNDS = 3            # rounds of the window that a traced run records
MAX_WARMUP_ROUNDS = 20
CACHE_DIR = cells.ROOT / ".jax_cache"
TRACE_DIR = cells.ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class WindowClosed(Exception):
    """Raised from ``on_round`` to end ``Federation.run`` after the window."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def setup_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed path, for
    every program however short its compile."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """Every executable JAX builds, by name, with its seconds and whether
    the persistent cache supplied it."""

    def __init__(self):
        self.events: list[tuple[str, float, bool]] = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self._hit = True

    def _on_duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((str(kw.get("fun_name", "?")), duration, self._hit))
            self._hit = False

    def mark(self) -> int:
        return len(self.events)

    def compiled(self, start: int, end: int | None = None) -> list:
        return [e for e in self.events[start:end] if not e[2]]

    def loaded(self, start: int, end: int | None = None) -> list:
        return [e for e in self.events[start:end] if e[2]]


def host_copy(tree):
    return jax.tree.map(np.asarray, tree)


def cohort_layout(assignment: dict, n_steps: dict, pad_multiple: int) -> list:
    """The cohorts of a round as ``fed/cohort.py::build_cohorts`` forms them,
    for clients with batches of one shape: one per tier, in the order of
    the tier's first client, members in client order, the client axis
    padded up to a multiple of ``pad_multiple``, every member padded to the
    cohort's most steps. Returns (tier, members, n_pad, steps) per cohort."""
    groups: dict[int, list] = {}
    for k in sorted(assignment):
        groups.setdefault(assignment[k], []).append(k)
    return [(t, m, (-len(m)) % pad_multiple, max(n_steps[k] for k in m))
            for t, m in groups.items()]


def chip_of(assignment: dict, pad_multiple: int) -> dict:
    """Client id -> chip under the sharded plane: each cohort's client axis
    is cut into ``pad_multiple`` equal, contiguous blocks, one per chip."""
    out = {}
    for _, members, n_pad, _ in cohort_layout(assignment, {k: 1 for k in assignment},
                                              pad_multiple):
        per = (len(members) + n_pad) // pad_multiple
        out.update({k: i // per for i, k in enumerate(members)})
    return out


class Driver:
    """``on_round`` for ``Federation.run``: keeps the first rounds' states,
    ends set-up when the tier assignment repeats, then times the window."""

    def __init__(self, fed, seconds: float, compiles: CompileLog, trace: bool):
        self.fed, self.seconds, self.compiles, self.trace = fed, seconds, compiles, trace
        self.states: list = []        # (params, aux) after each checked round
        self.logs: list = []
        self.seen: set = set()
        self.in_window = False
        self.window_logs: list = []
        self._spans: list = []
        self.t_last = time.perf_counter()

    def on_round(self, log) -> None:
        tr = self.fed.trainer
        jax.block_until_ready((tr.params, tr.aux))
        now = time.perf_counter()
        if self.in_window:
            self.window_logs.append(log)
            if now - self.t0 >= self.seconds or (
                    self.trace and len(self.window_logs) >= TRACE_ROUNDS):
                self.t1 = now
                raise WindowClosed
            self._next_round_span(log.round + 1)
            return
        self.logs.append(log)
        say(f"set-up round {log.round}: {now - self.t_last:.3f} s, tiers "
            f"{sorted(log.assignment.items())}, {len(self.compiles.compiled(0))} "
            f"compiled so far")
        self.t_last = now
        if len(self.states) < CHECKED_ROUNDS:
            self.states.append(host_copy((tr.params, tr.aux)))
        key = tuple(sorted(log.assignment.items()))
        repeated = key in self.seen
        self.seen.add(key)
        if len(self.logs) >= CHECKED_ROUNDS and repeated:
            self._open_window(log.round + 1)
        elif len(self.logs) >= MAX_WARMUP_ROUNDS:
            raise RuntimeError(f"tier assignment did not repeat in "
                               f"{MAX_WARMUP_ROUNDS} rounds: {log.assignment}")

    def _open_window(self, next_round: int) -> None:
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            self._spans.append(jax.profiler.TraceAnnotation("bench.window"))
            self._spans[-1].__enter__()
        self.mark = self.compiles.mark()
        self.in_window = True
        self.t0 = time.perf_counter()
        self._next_round_span(next_round)

    def _next_round_span(self, r: int) -> None:
        if not self.trace:
            return
        if len(self._spans) > 1:
            self._spans.pop().__exit__(None, None, None)
        self._spans.append(jax.profiler.StepTraceAnnotation("round", step_num=r))
        self._spans[-1].__enter__()

    def close_trace(self) -> dict | None:
        """Stop the profiler and read its trace; None without one."""
        if not self.trace:
            return None
        while self._spans:
            self._spans.pop().__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True)
        rec = devtrace.load(files[0]) if files else {}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return rec


def window_record(cell, fed, drv: Driver, compiles: CompileLog) -> dict:
    """What the window did, for the metric readers: samples, model FLOPs,
    client-steps computed and padded, compiles."""
    cfg = cell.config
    batch = cfg["spec"]["data"]["batch_size"]
    epochs = cfg["spec"]["trainer"].get("local_epochs", 1)
    pad_multiple = fed.trainer.exec_plan.pad_multiple
    samples = flop = computed = padded = 0
    for log in drv.window_logs:
        steps = {k: epochs * fed.clients[k].n_batches for k in log.assignment}
        for tier, members, n_pad, s_max in cohort_layout(log.assignment, steps,
                                                         pad_multiple):
            real = sum(steps[k] for k in members)
            computed += s_max * (len(members) + n_pad)
            padded += s_max * (len(members) + n_pad) - real
            flop += real * flops.client_step_flops(cell.arch, tier, batch)
        samples += sum(len(fed.clients[k].dataset) for k in log.assignment)
        if log.round % fed.spec.eval_every == 0:
            flop += flops.forward_flops(cell.arch, len(fed.eval_batch["labels"]))
    return {"rounds": len(drv.window_logs), "window_s": drv.t1 - drv.t0,
            "samples": samples, "model_flops": flop, "chips": cell.chips,
            "client_steps": computed, "padded_client_steps": padded,
            "compiles_window": len(compiles.compiled(drv.mark))}


def reference_inputs(fed, drv: Driver) -> dict:
    """The first rounds' tiers, batches and client weights, and the
    evaluation batch, as plain arrays (the reference's inputs)."""
    epochs = fed.spec.trainer.local_epochs
    rounds = []
    for log in drv.logs[:CHECKED_ROUNDS]:
        rounds.append({
            "assignment": dict(log.assignment),
            "batches": {k: materialize_round(fed.clients[k].dataset, log.round, epochs)
                        for k in log.assignment},
            "weights": {k: float(len(fed.clients[k].dataset)) for k in log.assignment}})
    return {"rounds": rounds,
            "eval": {k: np.asarray(v) for k, v in fed.eval_batch.items()},
            "pad_multiple": fed.trainer.exec_plan.pad_multiple,
            "lr": fed.spec.trainer.lr}


def run_reference(cell, init, inputs, *, dtype="float32", fault=None) -> list:
    """The reference's (params, aux) after each checked round."""
    devices = jax.devices()[:cell.chips]
    params, aux = init
    states = []
    for r, rnd in enumerate(inputs["rounds"]):
        t = time.perf_counter()
        params, aux = reference.run_round(
            cell.arch, params, aux, rnd["assignment"], rnd["batches"], rnd["weights"],
            lr=inputs["lr"], dtype=dtype, devices=devices, fault=fault,
            placement=chip_of(rnd["assignment"], inputs["pad_multiple"]))
        states.append(host_copy((params, aux)))
        say(f"reference round {r} ({dtype}, {fault or 'no fault'}): "
            f"{time.perf_counter() - t:.3f} s")
    return states


def build(cell, seed: int, reuse=None):
    """The cell's Federation, holding the benchmark's weights for ``seed``,
    and those weights (host copies). ``reuse``, a Federation of the same
    cell, lends its compiled programs."""
    fed = ExperimentSpec.from_dict(cell.spec_dict(seed)).build(reuse=reuse)
    params, aux = reference.init(seed, cell.arch)
    fed.trainer.load_state({"params": params, "aux": aux})
    return fed, host_copy((params, aux))


def drive(fed, seconds: float, compiles: CompileLog, trace: bool) -> Driver:
    drv = Driver(fed, seconds, compiles, trace)
    try:
        fed.run(on_round=drv.on_round)
    except WindowClosed:
        pass
    else:
        raise RuntimeError("Federation.run ended before the window closed")
    return drv


def device_info(cell) -> dict:
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True) -> dict:
    """One run; returns the result line's object (``checks`` last).

    ``require_tpu=False`` lets a test drive the rest of a run on the CPU,
    without the persistent cache and the table of peaks."""
    if require_tpu:
        setup_cache()
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise SystemExit(f"this cell needs {cell.chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    peak = cells.peaks(devs[0].device_kind) if require_tpu else None
    say(f"{cell.name}: {len(devs)} x {devs[0].device_kind} ({devs[0].platform}), "
        f"seed {seed}, {seconds:g} s window, trace {int(trace)}")
    compiles = CompileLog()

    fed, init = build(cell, seed)
    say(f"built the federation and its weights at {time.perf_counter() - t_start:.3f} s")
    drv = drive(fed, seconds, compiles, trace)
    setup_s = drv.t0 - t_start
    say(f"set-up {setup_s:.3f} s: {len(drv.logs)} rounds, tiers "
        f"{[sorted(l.assignment.items()) for l in drv.logs]}; "
        f"compiled {len(compiles.compiled(0, drv.mark))}, "
        f"loaded {len(compiles.loaded(0, drv.mark))}")
    for name, secs, hit in compiles.events[:drv.mark]:
        if secs >= 1.0:
            say(f"  set-up program {name}: {secs:.3f} s {'(cache)' if hit else '(compiled)'}")
    raw_trace = drv.close_trace()
    rec = window_record(cell, fed, drv, compiles)
    rec["peak"] = peak
    rec["trace"] = None
    device = device_info(cell)
    failed = sum(1 for l in drv.window_logs if not math.isfinite(l.acc))
    inputs = reference_inputs(fed, drv)
    states = drv.states
    del fed, drv
    gc.collect()

    result = {"attempted": rec["rounds"], "failed": failed, "device": device}
    if trace:
        red = devtrace.reduce(raw_trace or {})
        rec["trace"] = red
        result["metrics"] = per_layer(cell, rec)
        if red:
            used = [red["devices"][d] for d in sorted(red["devices"], key=int)[:cell.chips]]
            device["busy_s"] = sum(d["busy_s"] for d in used) / len(used)
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = {
            "samples_per_s": {"value": rec["samples"] / rec["window_s"], "unit": "samples/s"},
            "peak_hbm_gib": {"value": device["memory_peak_bytes"] / 2 ** 30, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    say(f"window: {rec['rounds']} rounds, {rec['samples']} samples in "
        f"{rec['window_s']:.3f} s, {rec['compiles_window']} compiles; "
        f"peak {device['memory_peak_bytes']} B")

    t = time.perf_counter()
    ref = run_reference(cell, init, inputs)
    nums = check.numbers(cell.arch, init, states, ref, inputs["eval"])
    say(f"reference: {time.perf_counter() - t:.3f} s; worst leaves {nums['_leaves']}")
    say("readings " + json.dumps({k: v for k, v in nums.items() if k != "_leaves"}))
    correct, checks = check.verdict(nums, cell.limits)
    result["correct"] = correct
    result["checks"] = checks
    for k, c in checks.items():
        say(f"check {k} = {c['value']:.6g} (limit {c['limit']:g})")
    return result


def per_layer(cell, rec: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        v = cells.reader(m["name"])(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def result_line(result: dict) -> str:
    order = ["correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"]
    return json.dumps({k: result[k] for k in order if k in result})
