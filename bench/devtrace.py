"""Reduction of a profiler trace to device busy time, idle gaps and collectives.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
record: the traced window (the host span ``bench.window``), each TPU's
operations (the ``XLA Ops`` line of its plane) and the spans of the host's
Python threads, all
as ``[name, start_ns, duration_ns]`` on the profiler's one clock. ``reduce``
works on that record alone, so a test can hold a small recorded one.
"""
from __future__ import annotations

import re

WINDOW_SPAN = "bench.window"
ROUND_SPAN = "round"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# the host threads that run Python: the benchmark's spans and JAX's
# dispatches (the runtime's own worker threads are left out)
HOST_LINES = ("python", "main")
# XLA's names for the operations that move data between chips
# control flow whose event spans the operations inside it
CONTROL_FLOW = re.compile(r"^%?(while|conditional|call)\b")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all", re.I)


def load(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[m.group(1)] = [[e.name.split(" = ")[0], e.start_ns,
                                            e.duration_ns] for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if not line.name.startswith(HOST_LINES):
                    continue
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = [e.start_ns, e.start_ns + e.duration_ns]
                    elif e.duration_ns > 0:
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"window": window, "devices": devices, "host": host}


def union(intervals) -> list[list[float]]:
    """Merge ``[start, end]`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _label(gap, host) -> str:
    """What the host did in an idle gap: the innermost host span (the
    shortest) among those that cover at least half of it, after the
    benchmark's round span that holds it."""
    best, best_d, outer = "no host span", float("inf"), ""
    for name, s, d in host:
        overlap = min(s + d, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        if name == ROUND_SPAN:
            outer = name + ": "
        elif 2 * overlap >= gap[1] - gap[0] and d < best_d:
            best, best_d = name, d
    return outer + best


def reduce(tr: dict, top: int = 10) -> dict:
    """Busy and idle time per device inside the window, collective time, the
    operations that took most time and the longest idle gaps (on the first
    device), each gap labelled by the host span that overlaps it most."""
    if not tr.get("window") or not tr.get("devices"):
        return {}
    lo, hi = tr["window"]
    window = hi - lo
    per_device, by_op, gaps = {}, {}, []
    first = min(tr["devices"], key=int)
    for dev in sorted(tr["devices"], key=int):
        clipped = list(_clip(tr["devices"][dev], lo, hi))
        busy = union([a, b] for _, a, b in clipped)
        busy_ns = sum(b - a for a, b in busy)
        coll_ns = sum(b - a for a, b in union(
            [a, b] for n, a, b in clipped if COLLECTIVE.search(n)))
        per_device[dev] = {"busy_s": busy_ns * 1e-9,
                           "idle_share": 1.0 - busy_ns / window,
                           "collective_s": coll_ns * 1e-9}
        if dev == first:
            for n, a, b in clipped:
                if not CONTROL_FLOW.match(n):
                    by_op[n] = by_op.get(n, 0.0) + (b - a) * 1e-9
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window * 1e-9,
        "devices": per_device,
        "device_ops": [[k, v] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(g, tr["host"]), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:top]],
    }
