"""The reduction of the program's spans and scopes, its readings, the
reading of each operation's ``op_name`` from a trace's HLO, and the report
that prints them."""
import glob

import jax
import jax.numpy as jnp
import pytest

import tinycell  # noqa: F401  (puts bench/ on the path)

import spanreport
import spans


def hand_made():
    """Window 0..1000 ns. Host: one round 0..900 holding plan 10..40,
    assemble 50..250 (a child span 60..100 in it), dispatch 260..300,
    aggregate 500..520 and eval 520..880; a span that is not the program's
    (``PjitFunction``) inside assemble. Chip 0 busy 300..500 (client),
    500..600 (server, backward), 600..650 (optimizer), 650..700 (an XLA
    copy, unscoped), 300..700 (the ``while`` around them, left out of the
    scopes), 800..850 (eval); chip 1 busy 0..1000."""
    ops = [["%while", 300, 400, "jit(dtfl_cohort_t0)/while"],
           ["%fusion.1", 300, 200, "jit(dtfl_cohort_t0)/while/body/closed_call/"
                                   "vmap(dtfl.client)/jvp()/dot_general"],
           ["%fusion.2", 500, 100, "jit(dtfl_cohort_t0)/while/body/"
                                   "transpose(jvp(dtfl.server))/conv"],
           ["%fusion.3", 600, 50, "jit(dtfl_cohort_t0)/while/body/vmap(dtfl.optimizer)/mul"],
           ["%copy.4", 650, 50, ""],
           ["%fusion.5", 800, 50, "jit(eval_acc)/eval/dot_general"]]
    return {"window": [0, 1000],
            "devices": {"0": [o[:3] for o in ops], "1": [["%fusion.9", 0, 1000]]},
            "scopes": {"0": [o[3] for o in ops], "1": [""]},
            "host": [["fed.round", 0, 900], ["fed.plan", 10, 30],
                     ["fed.assemble", 50, 200], ["fed.assemble", 60, 40],
                     ["PjitFunction(run)", 120, 10], ["fed.dispatch", 260, 40],
                     ["fed.aggregate", 500, 20], ["fed.eval", 520, 360],
                     ["python", 0, 1000]]}


def test_scope_of_takes_the_innermost_scope_through_transformations():
    assert spans.scope_of("jit(a)/while/body/transpose(jvp(dtfl.client))/dot") == "dtfl.client"
    assert spans.scope_of("jit(a)/vmap(dtfl.state)/dtfl.codec/convert") == "dtfl.codec"
    assert spans.scope_of("jit(eval_acc)/eval/conv") == "eval"
    assert spans.scope_of("jit(_wavg_cohorts)/aggregate/dot_general") == "aggregate"
    assert spans.scope_of("jit(dtfl_cohort_t0)/while/body/dynamic_update_slice") == "unscoped"
    assert spans.scope_of("") == "unscoped"


def test_hand_made_record():
    red = spans.reduce(hand_made())
    ns = pytest.approx
    rnd, asm = red["spans"]["fed.round"], red["spans"]["fed.assemble"]
    # the round's children cover 10..40, 50..250, 260..300, 500..880
    assert rnd["count"] == 1 and rnd["total_s"] == ns(900e-9)
    assert rnd["self_s"] == ns((900 - 30 - 200 - 40 - 380) * 1e-9)
    # assemble: two spans, the outer less its nested one; the host span
    # that is not the program's does not count as a child
    assert asm["count"] == 2
    assert asm["total_s"] == ns(240e-9) and asm["self_s"] == ns(200e-9)
    assert "PjitFunction(run)" not in red["spans"]
    # chip 0 idles 0..300, 700..800, 850..1000; inside self time:
    assert asm["idle_s"]["0"] == ns(200e-9)     # all of assemble's self time
    assert red["spans"]["fed.plan"]["idle_s"]["0"] == ns(30e-9)
    assert red["spans"]["fed.dispatch"]["idle_s"]["0"] == ns(40e-9)
    assert red["spans"]["fed.aggregate"]["idle_s"]["0"] == ns(0.0)
    assert red["spans"]["fed.eval"]["idle_s"]["0"] == ns(130e-9)  # 700..800, 850..880
    # the round's own time 0..10, 40..50, 250..260, 300..500, 880..900
    assert rnd["idle_s"]["0"] == ns(50e-9)
    assert all(r["idle_s"]["1"] == 0.0 for r in red["spans"].values())
    assert red["busy_s"] == {"0": ns(450e-9), "1": ns(1000e-9)}
    assert red["idle_s"]["0"] == ns(550e-9)
    # scopes on chip 0, the while left out
    assert red["scopes"] == {"dtfl.client": ns(200e-9), "dtfl.server": ns(100e-9),
                             "dtfl.optimizer": ns(50e-9), "unscoped": ns(50e-9),
                             "eval": ns(50e-9)}
    assert spans.scope_share(red, "dtfl.client") == ns(100 * 200 / 450)
    assert spans.scope_share(red, "dtfl.state") == 0.0
    assert spans.per_round_ms(red, ("fed.plan", "fed.assemble"), 2) == ns(1e3 * 230e-9 / 2)
    lines = spans.table(red)
    assert any(x.startswith("span fed.assemble: count 2") for x in lines)
    assert any(x.startswith("scope dtfl.client:") for x in lines)


def test_window_clips_spans_and_ops():
    tr = hand_made()
    tr["window"] = [100, 600]
    red = spans.reduce(tr)
    assert red["spans"]["fed.round"]["total_s"] == pytest.approx(500e-9)
    assert red["spans"]["fed.eval"]["total_s"] == pytest.approx(80e-9)
    assert "fed.plan" not in red["spans"]
    assert red["scopes"]["dtfl.client"] == pytest.approx(200e-9)
    assert red["scopes"]["dtfl.server"] == pytest.approx(100e-9)


def test_record_without_program_spans_or_scopes():
    """A program that opens no ``fed.*`` span and names no scope (the
    parent of this instrumentation) gives empty tables and no readings."""
    tr = hand_made()
    tr["host"] = [h for h in tr["host"] if not h[0].startswith("fed.")]
    del tr["scopes"]
    red = spans.reduce(tr)
    assert red["spans"] == {} and red["scopes"] == {}
    assert spans.readings(red, 3, None) == dict.fromkeys(
        ["h2d.mib_per_round", *spans.READINGS])
    tr["scopes"] = {"0": [""] * len(tr["devices"]["0"])}
    assert spans.scope_share(spans.reduce(tr), "dtfl.client") is None
    assert spans.reduce({"window": None, "devices": {}, "host": []}) == {}


def test_readings_without_a_trace_read_only_the_counter():
    read = spans.readings({}, 13, 13 * 2 ** 20)
    assert read.pop("h2d.mib_per_round") == 1.0
    assert set(read) == set(spans.READINGS) and set(read.values()) == {None}


def test_readings_on_the_hand_made_record():
    read = spans.readings(spans.reduce(hand_made()), 1, 12_591_136)
    assert read["host.schedule_ms"] == pytest.approx(30e-6)
    assert read["host.assemble_ms"] == pytest.approx(200e-6)
    assert read["host.dispatch_ms"] == pytest.approx(40e-6)
    assert read["host.aggregate_ms"] == pytest.approx(20e-6)
    assert read["h2d.mib_per_round"] == pytest.approx(12.0078, abs=1e-4)
    assert read["device.client_pass_share"] == pytest.approx(100 * 200 / 450)
    assert read["device.server_pass_share"] == pytest.approx(100 * 100 / 450)
    assert read["device.optimizer_share"] == pytest.approx(100 * 50 / 450)
    assert read["device.cohort_state_share"] == 0.0


def test_op_names_read_from_a_trace(tmp_path):
    """``spans`` reads each instruction's ``op_name`` out of the HLO
    that the trace's metadata plane holds (a CPU trace here: it has no TPU
    plane, so only the table is checked)."""
    def f(x):
        with jax.named_scope("dtfl.server"):
            return jax.grad(lambda y: jnp.sum(jnp.tanh(y @ y)))(x)

    f.__name__ = "dtfl_cohort_t5"
    g = jax.jit(f)
    x = jnp.ones((8, 8))
    g(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        g(x).block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    tables = spans._hlo_op_names(path)
    (name,) = [k for k in tables if k.startswith("jit_dtfl_cohort_t5(")]
    paths = set(tables[name].values())
    assert any(p.startswith("jit(dtfl_cohort_t5)/dtfl.server/transpose(jvp())/")
               for p in paths)
    assert {spans.scope_of(p) for p in paths if p.startswith("jit(")} == {"dtfl.server"}


def test_ops_take_the_table_of_the_module_that_holds_them():
    ops = [["%fusion.1", 10, 5], ["%fusion.1", 30, 5], ["%copy", 50, 5]]
    modules = [[0, 20, "jit_a(1)"], [25, 40, "jit_b(2)"]]
    tables = {"jit_a(1)": {"fusion.1": "jit(a)/dtfl.client/x"},
              "jit_b(2)": {"fusion.1": "jit(b)/dtfl.server/x"}}
    assert spans._scopes(ops, modules, tables) == [
        "jit(a)/dtfl.client/x", "jit(b)/dtfl.server/x", ""]


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _ins(name, iid, op_name="", operands=(), called=()):
    body = _field(1, name) + _field(35, iid)
    if op_name:
        body += _field(7, _field(2, op_name))
    if operands:                                   # packed, as XLA writes them
        body += _field(36, b"".join(map(_varint, operands)))
    for c in called:                               # unpacked also reads
        body += _field(38, c)
    return _field(2, body)


def test_instructions_without_metadata_take_it_from_inside_or_from_operands():
    """XLA leaves some instructions it makes without ``op_name``: a fusion
    root it rewrote takes the last named instruction of its fused
    computation, a copy that of its operand, and what is left that of the
    instruction it feeds. The caller comes first in the module here, so the
    order of computations does not matter."""
    server = "jit(t)/while/body/vmap(dtfl.server)/jvp()/pad"
    entry = _field(3, _ins("p.0", 10) + _ins("fusion.1", 11, operands=[10], called=[1])
                   + _ins("copy.2", 12, operands=[11]) + _ins("tuple.3", 13)
                   + _field(5, 2))
    fused = _field(3, _ins("param_0", 1) + _ins("pad.4", 2, server, operands=[1])
                   + _ins("dus.5", 3, operands=[1, 2]) + _field(5, 1))
    names = spans._module_op_names(memoryview(_field(1, "jit_t") + entry + fused))
    assert names == {"p.0": server, "fusion.1": server, "copy.2": server, "tuple.3": "",
                     "param_0": server, "pad.4": server, "dus.5": server}


def test_report_on_the_cpu(capsys):
    """The report drives the benchmark's set-up and traced window on the
    tiny cell: the counter reads, the device readings (no TPU plane on the
    CPU) do not. One fixed tier, so set-up compiles one program."""
    cell = tinycell.tiny("cohort.iid")
    cell.config["spec"]["trainer"]["scheduler"] = 1
    out = spanreport.report(cell, seed=5, require_tpu=False)
    # 4 clients x 16 samples of 16x16x3 float32, an int32 label and a
    # float32 mask each, and a bool step mask of 2 steps x 4 clients
    per_round = (4 * 16 * (16 * 16 * 3 * 4 + 4 + 4) + 2 * 4) / 2 ** 20
    assert out["rounds"] == 3 and out["window_s"] > 0
    assert out["readings"].pop("h2d.mib_per_round") == pytest.approx(per_round)
    assert set(out["readings"].values()) == {None}
