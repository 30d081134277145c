"""``BENCHMARK.json`` and the files it names: every piece resolves by
name, the file keeps to its contract, and a new configuration, traffic
mix or per-layer metric is found by adding files and entries only."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from bench_testlib import BENCH, ROOT
from harness.cells import CellError, load_cell, peaks_for
from harness.state import Run

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert isinstance(BENCHMARK["run_seconds"], int)
    assert 1 <= BENCHMARK["run_seconds"] <= 51


def test_entries_keep_their_shapes():
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert (ROOT / c["file"]).is_file()
    for w in BENCHMARK["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCHMARK["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCHMARK["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    every = (BENCHMARK["configs"] + BENCHMARK["workloads"]
             + BENCHMARK["end_to_end"] + BENCHMARK["per_layer"])
    for entry in every:
        assert NAME.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert UNIT.match(entry["unit"]) and entry["better"] in (
                "lower", "higher")
    names = [e["name"] for e in every]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_resolves_and_reports_enough(workload):
    cell = load_cell(workload)
    assert (BENCH / "harness" / f"{cell.traffic['driver']}.py").is_file()
    needs = {"solve_loop": ("make_data", "smoothness", "solve"),
             "serve_loop": ("certify",)}[cell.traffic["driver"]]
    ref = cell.reference()
    assert all(callable(getattr(ref, f, None))
               for f in needs + ("expected_ledger",))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(cell.reader(m["name"]).read)


def test_roofline_names_follow_the_rule():
    for m in BENCHMARK["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_unknown_device_kind_is_an_error():
    row = peaks_for("TPU v5 lite")
    assert row["flops_bf16"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(CellError, match="no peaks for device kind"):
        peaks_for("TPU v99")


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later change adds three files and three entries, and edits no
    file that is there."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs, bench_dir = tmp_path / "bench" / "configs", tmp_path / "bench"
    cfg = json.loads((configs / "epsilon-logistic.json").read_text())
    cfg["instance_params"]["n"] = 100_000
    (configs / "added-config.json").write_text(json.dumps(cfg))
    shutil.copy(configs / "epsilon-logistic.reference.py",
                configs / "added-config.reference.py")
    (bench_dir / "traffic" / "added-mix.json").write_text(json.dumps(
        dict(driver="solve_loop", algorithm="dgd", channel="fp16",
             rounds=50, eps=[1e-4], eps_mode="rel")))
    (bench_dir / "metrics" / "added_metric.py").write_text(
        "def read(run):\n    return run.counters.get('rounds')\n")
    bench["configs"].append(dict(name="added-config", source="x",
                                 file="bench/configs/added-config.json",
                                 reduced=[], why="x"))
    bench["workloads"].append(dict(name="added-cell", config="added-config",
                                   traffic="added-mix", chips=1, why="x"))
    bench["per_layer"].append(dict(
        name="added_metric", unit="rounds", better="higher",
        source="host_clock", layer="x", moves="rounds_per_s",
        workloads=["added-cell"]))
    bench["end_to_end"][1]["workloads"].append("added-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell("added-cell", tmp_path)
    assert cell.config["instance_params"]["n"] == 100_000
    assert cell.traffic["algorithm"] == "dgd"
    assert [m["name"] for m in cell.per_layer][-1] == "added_metric"
    run = Run(cell=cell, seed=1, seconds=1.0, trace=True, start=0.0,
              work_dir=tmp_path, counters={"rounds": 123})
    assert cell.reader("added_metric").read(run) == 123
    assert load_cell("epsilon-dagd", tmp_path).config_name == \
        "epsilon-logistic"
    with pytest.raises(CellError, match="no workload"):
        load_cell("not-a-cell", tmp_path)
