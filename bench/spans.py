"""The program's own spans and scopes in a traced window.

The program opens host spans named ``fed.*`` (``jax.profiler.TraceAnnotation``
in ``fed/engine.py``, ``fed/dtfl.py``, ``fed/base.py``, ``fed/cohort.py``)
and names scopes inside its device programs (``jax.named_scope``:
``dtfl.client``, ``dtfl.server``, ``dtfl.optimizer``, ``dtfl.codec``,
``dtfl.state``, ``eval``, ``aggregate``). ``load`` reads a trace into the
record of ``devtrace.load`` with one more key, ``scopes``: each operation's
``op_name`` (its HLO metadata), in the order of the operations. ``reduce``
works on that record alone, so a test can hand-make one:

- per span name, inside the window: how many, their total and self time
  (a span's duration less what its child spans cover), and the time each
  chip sat idle inside that self time;
- per scope, on the first chip: the device time of its operations
  (control flow left out), by the innermost scope on each operation's
  ``op_name``; an operation under none of them is ``unscoped``. A fusion
  carries the ``op_name`` of its root (``load`` fills in one that XLA
  left without), so its whole time goes there.

A record of a program without spans or scopes gives empty tables.
"""
from __future__ import annotations

import bisect
import re

import devtrace

SPAN = re.compile(r"^fed\.")
SCOPE = re.compile(r"^(dtfl\.\w+|eval|aggregate)$")
ROUND = "fed.round"
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
# the plane that holds each loaded program's optimized HLO (a serialized
# HloProto per program, named like its ``XLA Modules`` events)
METADATA_PLANE = "/host:metadata"
# a transformation's wrapping of a scope on the name path, such as
# ``transpose(jvp(dtfl.client))`` or ``vmap(dtfl.client)``
_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+$")


def load(path: str) -> dict:
    """``devtrace.load``'s record of the trace at ``path``, with ``scopes``:
    per chip, each operation's ``op_name``, looked up in the table of the
    program whose ``XLA Modules`` event holds the operation's start ("" where
    none does, or the program's HLO is not in the trace)."""
    import jax

    rec = devtrace.load(path)
    op_names = _hlo_op_names(path)
    rec["scopes"] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        modules = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = sorted([e.start_ns, e.start_ns + e.duration_ns, e.name]
                                 for e in line.events)
        rec["scopes"][m.group(1)] = _scopes(rec["devices"].get(m.group(1), []),
                                            modules, op_names)
    return rec


def _scopes(ops, modules, op_names) -> list[str]:
    """Each operation's ``op_name`` from the table of the module that holds it."""
    starts = [m[0] for m in modules]
    out = []
    for name, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        table = op_names.get(modules[i][2], {}) if i >= 0 and s < modules[i][1] else {}
        out.append(table.get(name.lstrip("%"), ""))
    return out


def _hlo_op_names(path: str) -> dict:
    """Program name -> {HLO instruction name: op_name} from the HloProtos of
    the trace's metadata plane. The ``.xplane.pb`` is read field by field
    (XSpace.planes 1; XPlane.name 2, event_metadata 4; XEventMetadata.name
    2, stats 5; XStat.bytes_value 6; HloProto.hlo_module 1)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for plane in _values(buf, 1):
        if bytes(_first(plane, 2) or b"") != METADATA_PLANE.encode():
            continue
        for entry in _values(plane, 4):               # map entry: key 1, value 2
            meta = _first(entry, 2)
            for stat in _values(meta, 5):
                proto = _first(stat, 6)
                if proto is not None:
                    out[bytes(_first(meta, 2) or b"").decode()] = _module_op_names(
                        _first(proto, 1) or b"")
    return out


def _module_op_names(module) -> dict:
    """Instruction name -> op_name for one HloModuleProto (computations 3:
    id 5, instructions 2: name 1, metadata 7 (OpMetadata.op_name 2), id 35,
    operand_ids 36, called_computation_ids 38). An instruction that XLA made
    without metadata (a fusion root it rewrote, a tuple, a copy it put in)
    takes the op_name of the last instruction with one in the computation it
    calls, else that of its first operand with one, else that of the first
    instruction it feeds (a copy of loop state into fast memory)."""
    comps = {}
    for comp in _values(module, 3):
        rows, cid = [], 0
        for num, v in _fields(comp):
            if num == 5:
                cid = v
            elif num == 2:
                name, op_name, iid, operands, called = b"", "", None, [], []
                for n, x in _fields(v):
                    if n == 1:
                        name = x
                    elif n == 7:
                        op_name = bytes(_first(x, 2) or b"").decode()
                    elif n == 35:
                        iid = x
                    elif n == 36:
                        operands += _ints(x)
                    elif n == 38:
                        called += _ints(x)
                rows.append((bytes(name).decode(), op_name, iid, operands, called))
        comps[cid] = rows
    names, by_id, last = {}, {}, {}

    def resolve(cid):            # the op_name of the computation's last named instruction
        if cid not in last:
            last[cid], users = "", {}
            rows = comps.get(cid, [])
            for name, op_name, iid, operands, called in rows:
                if not op_name:
                    op_name = (next((x for x in map(resolve, called) if x), "")
                               or next((by_id[o] for o in operands if by_id.get(o)), ""))
                names[name] = by_id[iid] = op_name
                last[cid] = op_name or last[cid]
                for o in operands:
                    users.setdefault(o, []).append(iid)
            for name, _, iid, _, _ in reversed(rows):
                if not by_id[iid]:
                    names[name] = by_id[iid] = next(
                        (by_id[u] for u in users.get(iid, ()) if by_id[u]), "")
        return last[cid]

    for cid in comps:
        resolve(cid)
    return names


def _ints(v) -> list[int]:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message;
    length-delimited values as views, fixed-width ones as None."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _values(buf, num: int):
    return (v for k, v in _fields(buf) if k == num)


def _first(buf, num: int):
    return next(_values(buf, num), None)


def scope_of(op_name: str) -> str:
    """The innermost program scope on an operation's ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        part = _WRAPPERS.sub("", part)
        if SCOPE.match(part):
            return part
    return UNSCOPED


def _minus(span, cover):
    """``span`` ([start, end]) less the sorted disjoint intervals ``cover``."""
    out, at = [], span[0]
    for a, b in cover:
        if a > at:
            out.append([at, min(a, span[1])])
        at = max(at, b)
    if at < span[1]:
        out.append([at, span[1]])
    return [iv for iv in out if iv[1] > iv[0]]


def _overlap(xs, ys) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _self_intervals(spans):
    """Each span's [name, [start, end], self intervals]: spans nest (one
    thread opens them), so a stack finds each one's children."""
    out, stack = [], []
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1][1] <= a:
            stack.pop()
        node = [name, [a, b], []]
        if stack:
            stack[-1][2].append([a, b])
        out.append(node)
        stack.append(node)
    return [[name, iv, _minus(iv, devtrace.union(children))]
            for name, iv, children in out]


def reduce(tr: dict) -> dict:
    """Span and scope tables of the window (empty without one), with each
    chip's busy and idle seconds in the window."""
    if not tr.get("window") or not tr.get("devices"):
        return {}
    lo, hi = tr["window"]
    ids = sorted(tr["devices"], key=int)
    idle, busy_s = {}, {}
    for dev in ids:
        busy = devtrace.union([a, b] for _, a, b in devtrace._clip(tr["devices"][dev], lo, hi))
        busy_s[dev] = sum(b - a for a, b in busy) * 1e-9
        idle[dev] = _minus([lo, hi], busy)
    program = [[n, a, b] for n, a, b in devtrace._clip(tr["host"], lo, hi) if SPAN.match(n)]
    spans: dict[str, dict] = {}
    for name, (a, b), own in _self_intervals(program):
        row = spans.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0,
                                      "idle_s": {d: 0.0 for d in ids}})
        row["count"] += 1
        row["total_s"] += (b - a) * 1e-9
        row["self_s"] += sum(y - x for x, y in own) * 1e-9
        for d in ids:
            row["idle_s"][d] += _overlap(own, idle[d]) * 1e-9
    first = ids[0]
    scopes: dict[str, float] = {}
    names = tr.get("scopes", {}).get(first)
    if names and any(names):
        clipped = ((n, max(s, lo), min(s + d, hi), path)
                   for (n, s, d), path in zip(tr["devices"][first], names))
        for n, a, b, path in clipped:
            if b > a and not devtrace.CONTROL_FLOW.match(n):
                key = scope_of(path)
                scopes[key] = scopes.get(key, 0.0) + (b - a) * 1e-9
    return {"spans": spans, "scopes": scopes, "busy_s": busy_s,
            "idle_s": {d: (hi - lo) * 1e-9 - busy_s[d] for d in ids}}


def per_round_ms(red: dict, names, rounds: int) -> float | None:
    """Self time of the spans ``names`` per round, in ms; None where the
    program opened none of them."""
    rows = [red["spans"][n] for n in names if n in (red or {}).get("spans", {})]
    if not rows or not rounds:
        return None
    return 1e3 * sum(r["self_s"] for r in rows) / rounds


def scope_share(red: dict, scope: str) -> float | None:
    """``scope``'s device time over the first chip's busy time, in per cent;
    None where no operation carries a program scope."""
    scopes = (red or {}).get("scopes", {})
    if not any(k != UNSCOPED for k in scopes):
        return None
    busy = red["busy_s"][min(red["busy_s"], key=int)]
    return 100.0 * scopes.get(scope, 0.0) / busy if busy > 0 else None


# the per-layer readings of the tables: span self time per round, in ms
# (names of spans), or a scope's share of the first chip's busy time
READINGS = {
    "host.schedule_ms": ("fed.plan", "fed.observe"),
    "host.assemble_ms": ("fed.assemble",),
    "host.dispatch_ms": ("fed.dispatch",),
    "host.aggregate_ms": ("fed.aggregate",),
    "device.client_pass_share": "dtfl.client",
    "device.server_pass_share": "dtfl.server",
    "device.optimizer_share": "dtfl.optimizer",
    "device.cohort_state_share": "dtfl.state",
}


def readings(red: dict, rounds: int, h2d_bytes: int | None) -> dict:
    """The readings of ``READINGS`` and ``h2d.mib_per_round`` (the program's
    ``RoundLog.h2d_bytes`` summed over ``rounds`` rounds, per round); each
    None where the trace or the program gives nothing to read."""
    out = {"h2d.mib_per_round": (h2d_bytes / rounds / 2 ** 20
                                 if h2d_bytes is not None and rounds else None)}
    for name, what in READINGS.items():
        if not red:
            out[name] = None
        elif isinstance(what, tuple):
            out[name] = per_round_ms(red, what, rounds)
        else:
            out[name] = scope_share(red, what)
    return out


def table(red: dict) -> list[str]:
    """The span and scope tables as lines, for standard error: each span's
    count, total and self time and the first chip's idle time in its self
    time (share of the window's idle); each scope's time and share of busy."""
    if not red:
        return []
    first = min(red["busy_s"], key=int)
    idle, busy = red["idle_s"][first], red["busy_s"][first]
    lines = []
    for name, row in sorted(red["spans"].items()):
        i = row["idle_s"][first]
        lines.append(f"span {name}: count {row['count']}, total {row['total_s']:.6f} s, "
                     f"self {row['self_s']:.6f} s, chip {first} idle in self "
                     f"{i:.6f} s ({100 * i / idle if idle > 0 else 0:.1f}% of idle)")
    inside = sum(r["idle_s"][first] for n, r in red["spans"].items() if n != ROUND)
    lines.append(f"span fed.* but {ROUND}: chip {first} idle in self {inside:.6f} s "
                 f"of {idle:.6f} s ({100 * inside / idle if idle > 0 else 0:.1f}%)")
    for name, secs in sorted(red["scopes"].items(), key=lambda kv: -kv[1]):
        lines.append(f"scope {name}: {secs:.6f} s, "
                     f"{100 * secs / busy if busy > 0 else 0:.1f}% of chip {first} busy")
    return lines
