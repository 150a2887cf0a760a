"""Cells, configurations, traffic mixes and metric readers are found by name."""

import ast
import json
import shutil

import pytest

from bench.lib import cells
from bench.lib.context import RunContext


def _declared(path):
    """The UNIT/BETTER/SOURCE/LAYER/MOVES constants of a reader file."""
    out = {}
    for node in ast.parse(path.read_text()).body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Tuple):
                    for name, value in zip(t.elts, ast.literal_eval(node.value)):
                        out[name.id] = value
                elif isinstance(t, ast.Name):
                    try:
                        out[t.id] = ast.literal_eval(node.value)
                    except ValueError:
                        pass
    return out


def test_every_cell_loads_its_files_by_name():
    spec = cells.benchmark()
    for w in spec["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.config is not None and cell.traffic is not None
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    names = {c["name"] for c in spec["configs"]}
    assert names == {w["config"] for w in spec["workloads"]}


def test_every_metric_has_a_reader_that_states_what_the_json_says():
    spec = cells.benchmark()
    for m in spec["end_to_end"] + spec["per_layer"]:
        base = m["name"].split(".", 1)[0]
        d = _declared(cells.BENCH / "metrics" / f"{base}.py")
        assert d["UNIT"] == m["unit"] and d["BETTER"] == m["better"]
        assert d["SOURCE"] == m["source"]
        assert d.get("LAYER") == m.get("layer")
        if base == m["name"]:      # a split name moves its own cells' metric
            assert d.get("MOVES") == m.get("moves")
        assert callable(cells.metric_reader(m["name"]).read)


def test_per_layer_cells_report_what_they_move():
    spec = cells.benchmark()
    for m in spec["per_layer"]:
        for w in m["workloads"]:
            e2e = [e["name"] for e in cells.load_cell(w).end_to_end]
            assert m["moves"] in e2e, (m["name"], w)


def test_a_metric_added_as_a_file_is_found(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(cells.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = cells.benchmark()
    spec["per_layer"].append({
        "name": "policy_calls", "unit": "calls", "better": "lower",
        "source": "program_counter", "layer": "fit",
        "moves": "suggestions_per_s.steady", "workloads": ["sparse.steady"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "metrics" / "policy_calls.py").write_text(
        'UNIT, BETTER, SOURCE = "calls", "lower", "program_counter"\n'
        'LAYER, MOVES = "fit", "suggestions_per_s.steady"\n'
        "def read(ctx):\n    return float(len(ctx.recorder.calls))\n")
    monkeypatch.setattr(cells, "BENCH", bench)
    monkeypatch.setattr(cells, "ROOT", tmp_path)
    cell = cells.load_cell("sparse.steady")
    assert "policy_calls" in [m["name"] for m in cell.per_layer]

    class Rec:
        calls = [1, 2, 3]

    ctx = RunContext(cell=cell, plan=None, seconds=1.0, t_proc=0.0, t0=1.0,
                     close=1.0, grace_s=1.0, records=[], unfinished_due=[],
                     late_s=[], recorder=Rec())
    assert cells.metric_reader("policy_calls").read(ctx) == 3.0


def test_a_traffic_mix_and_config_added_as_files_are_found(tmp_path,
                                                           monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(cells.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = cells.benchmark()
    spec["workloads"].append({"name": "sparse.burst", "config": "bbob-big",
                              "traffic": "sparse-burst", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copy(bench / "traffic" / "sparse-steady.json",
                bench / "traffic" / "sparse-burst.json")
    shutil.copy(bench / "configs" / "bbob-f10-d20-sparse.json",
                bench / "configs" / "bbob-big.json")
    monkeypatch.setattr(cells, "BENCH", bench)
    monkeypatch.setattr(cells, "ROOT", tmp_path)
    cell = cells.load_cell("sparse.burst")
    assert cell.traffic_name == "sparse-burst"
    assert cell.config_name == "bbob-big"


def test_a_split_name_reads_with_its_base_reader():
    assert (cells.metric_reader("fit_ms.steady").MODULES
            == cells.metric_reader("fit_ms").MODULES)


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        cells.load_cell("no.such.cell")
    with pytest.raises(FileNotFoundError):
        cells.metric_reader("no_such_metric")
