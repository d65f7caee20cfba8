"""BENCHMARK.json against the contract's rules, and discovery by name."""
import json
import re

import pytest

from conftest import BENCH, ROOT, make_root

from bench.generators import CONTRACT
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
    assert cell_rule_breaches(b) == []


def cell_rule_breaches(b) -> list[str]:
    """The contract's rules on the cells: 1 or 4 chips, at most half of the
    cells (rounded down, and one always) on four, a ``why`` of at most 200
    characters on one line."""
    cells = b["workloads"]
    out = [f"{w['name']}: chips {w['chips']}" for w in cells
           if w["chips"] not in (1, 4)]
    out += [f"{w['name']}: why" for w in cells
            if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]]
    four = sum(w["chips"] == 4 for w in cells)
    if four > max(1, len(cells) // 2):
        out.append(f"{four} of {len(cells)} cells on four chips")
    return out


def test_every_cell_resolves_with_its_metrics():
    for w in benchmark()["workloads"]:
        cell = load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert set(cell.readers) == e2e | {m["name"] for m in cell.per_layer}
        assert set(cell.limits) == {"solve_gap", "probe_gap", "grad_gap",
                                   "adam_gap", "step1_gap"}
        assert (BENCH / "generators"
                / f"{cell.traffic['generator']}.py").exists()
        assert all(callable(getattr(cell.generator, f)) for f in CONTRACT)


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


def test_a_four_chip_cell_with_its_own_generator_needs_only_new_files(
        tmp_path):
    """A cell on four chips, whose traffic names a generator of its own, is
    added by new files and a new BENCHMARK.json entry alone."""
    root = make_root(tmp_path)
    bench = root / "bench"
    stubs = "\n\n".join(f"def {f}(*args):\n    return {f!r}\n"
                        for f in CONTRACT)
    (bench / "generators" / "dummy_rows.py").write_text(stubs)
    traffic = json.loads((BENCH / "traffic" / "fit_budget.json").read_text())
    traffic["generator"] = "dummy_rows"
    (bench / "traffic" / "dummy_rows4.json").write_text(json.dumps(traffic))
    (bench / "limits" / "tiny.dummy_rows4.json").write_text(
        (BENCH / "limits" / "3droad.fit_budget.json").read_text())
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny.dummy_rows4", "config": "tiny",
                           "traffic": "dummy_rows4", "chips": 4,
                           "why": "test: rows sharded over four chips"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = load_cell("tiny.dummy_rows4", root=root)
    assert cell.chips == 4
    assert cell.generator.window_programs(cell) == "window_programs"
    assert cell.generator.__file__ == str(bench / "generators"
                                          / "dummy_rows.py")
    assert cell_rule_breaches(b) == []
    # A generator without every function of the contract is refused.
    (bench / "generators" / "dummy_rows.py").write_text(
        stubs.replace("def window_programs", "def other"))
    with pytest.raises(AttributeError, match="window_programs"):
        load_cell("tiny.dummy_rows4", root=root)


@pytest.mark.parametrize("chips,breaches", [
    ([1, 1, 4], 0),
    ([1, 4, 4], 1),  # a second four-chip cell among three
    ([1, 1, 4, 4], 0),
    ([4], 0),  # one always may
    ([4, 4], 1),
    ([1, 2], 1),
])
def test_cell_rules(chips, breaches):
    b = {"workloads": [{"name": f"c{i}", "chips": c, "why": "w"}
                       for i, c in enumerate(chips)]}
    assert len(cell_rule_breaches(b)) == breaches
    b["workloads"][0]["why"] = "w" * 201
    assert len(cell_rule_breaches(b)) == breaches + 1
