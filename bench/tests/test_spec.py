"""BENCHMARK.json against the contract's rules, and discovery by name."""
import json
import re

from conftest import BENCH, ROOT, make_root

from bench.spec import load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def benchmark():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_command():
    b = benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"]
    assert b["command"] == ["python3", "bench/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_sources():
    b = benchmark()
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    names += [c["name"] for c in b["configs"]]
    names += [w["name"] for w in b["workloads"]]
    assert all(NAME.match(n) for n in names), names
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


def test_configs_files_and_cells():
    b = benchmark()
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert c["file"].startswith("bench/")
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["name"] == c["name"]
        assert config["reduced"] == c["reduced"]
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] == 1 and len(w["why"]) <= 200
               for w in b["workloads"])


def test_every_cell_resolves_with_its_metrics():
    for w in benchmark()["workloads"]:
        cell = load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert set(cell.readers) == e2e | {m["name"] for m in cell.per_layer}
        assert set(cell.limits) == {"solve_gap", "probe_gap", "grad_gap",
                                   "adam_gap", "step1_gap"}
        assert cell.traffic["generator"] == "closed_loop_fits"


def test_budget_metric_only_where_listed():
    assert "budget_res_norm" not in load_cell("pol.fit_tol").readers
    assert "budget_res_norm" in load_cell("3droad.fit_budget").readers


def test_a_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    """A dummy configuration, traffic mix and per-layer metric are added
    by new files and new BENCHMARK.json entries alone."""
    root = make_root(tmp_path)
    bench = root / "bench"
    (bench / "configs" / "dummy.json").write_text(
        (bench / "configs" / "tiny.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "fit_budget.json").read_text())
    traffic["max_epochs"] = 3
    (bench / "traffic" / "dummy_mix.json").write_text(json.dumps(traffic))
    (bench / "limits" / "dummy.dummy_mix.json").write_text(
        (BENCH / "limits" / "pol.fit_tol.json").read_text())
    (bench / "layer_metrics" / "dummy_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "dummy", "source": "test",
                         "file": "bench/configs/dummy.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "dummy.dummy_mix", "config": "dummy",
                           "traffic": "dummy_mix", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "dummy_metric", "unit": "x",
                           "better": "lower", "source": "program_counter",
                           "layer": "test", "moves": "fit_steps_per_s",
                           "workloads": ["dummy.dummy_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = load_cell("dummy.dummy_mix", root=root)
    assert cell.traffic["max_epochs"] == 3
    assert cell.readers["dummy_metric"]({}) == 42.0
    assert "dummy_metric" not in load_cell("tiny.fit_tol", root=root).readers
