"""BENCHMARK.json keeps to its contract, and the harness finds every
cell's configuration, traffic, limits and metric readers by name."""
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]

from harness import runner, spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    assert os.path.exists(os.path.join(spec.ROOT, bench["command"][1]))


def test_names_units_and_bounds(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["layer"]


@pytest.mark.parametrize("workload", [
    w["name"] for w in spec.load_benchmark()["workloads"]])
def test_cell_pieces_found_by_name(bench, workload):
    cell = spec.cell(workload, bench)
    assert cell["chips"] in (1, 4)
    cfg = cell["config_spec"]
    assert cfg["name"] == cell["config"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["file"] == f"benchmarks/chip/configs/{cfg['name']}.json"
    assert cfg["source"] == entry["source"]
    assert set(entry["reduced"]) <= set(cfg.get("changed", {}))
    assert hasattr(spec.reference(cfg), "init_params")
    assert spec.limits(workload)
    e2e = spec.metrics_for(workload, bench["end_to_end"])
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    per_layer = spec.metrics_for(workload, bench["per_layer"])
    assert per_layer
    for m in per_layer:
        assert callable(spec.metric_reader(m["name"]))
        moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        assert "workloads" not in moved or workload in moved["workloads"]


def test_every_config_is_used(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_unknown_names_are_refused(bench):
    with pytest.raises(spec.SpecError):
        spec.cell("no-such-cell", bench)
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no.such.metric")


def test_refuses_a_machine_without_a_tpu(capsys):
    with pytest.raises(runner.NoChip):
        runner.check_devices(1)
    w = spec.load_benchmark()["workloads"][0]["name"]
    rc = runner.main(["--workload", w, "--seed", "1", "--seconds", "1",
                      "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err


def test_peak_table_keyed_by_device_kind():
    table = runner.peaks()
    v5e = table["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
