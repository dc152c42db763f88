"""Every cell, configuration, traffic mix, limits file and metric reader that
BENCHMARK.json names loads, and the file keeps to the benchmark's format."""
import re

import pytest

import tinycell  # noqa: F401  (puts bench/ and src/ on the path)

import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = cells.benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_loads(name):
    cell = cells.load(name)
    assert cell.chips in (1, 4)
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    assert cell.traffic["exec"]["mode"] in ("cohort", "sharded", "chunked")
    spec = cell.spec_dict(2 ** 31 + 11)
    from repro.api import ExperimentSpec

    assert ExperimentSpec.from_dict(spec).seed == 2 ** 31 + 11
    for m in cell.per_layer:
        assert callable(cells.reader(m["name"]))


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_matches_the_program_model(conf):
    """The reference's shapes (``arch``) are the program's model at full size."""
    from repro.configs.resnet_cifar import get_resnet

    c = cells._json(cells.ROOT / conf["file"])
    prog = get_resnet(c["spec"]["model"]["arch"])
    assert c["spec"]["model"]["full_size"] is True
    assert c["arch"] == {k: getattr(prog, k) for k in c["arch"]}
    assert set(conf["reduced"]) == set(c["reduced"])


def test_peaks_known_and_unknown_device():
    v5e = cells.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        cells.peaks("TPU v9 imaginary")
