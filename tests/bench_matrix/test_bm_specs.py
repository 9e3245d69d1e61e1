"""The benchmark's data files: every one loads, every name resolves, and
`BENCHMARK.json` mirrors the directories. A fifth cell is new files only."""

import json
import shutil
from pathlib import Path

import pytest

from bench_matrix import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = spec.names("workloads")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.mark.parametrize("kind", ["configs", "traffic", "workloads", "layer_metrics"])
def test_every_file_loads_and_is_named_within_the_contract(kind):
    found = spec.names(kind)
    assert found
    for name in found:
        assert spec.NAME.match(name), name
        assert isinstance(spec.load(kind, name), dict)


@pytest.mark.parametrize("name", CELLS)
def test_cell_cross_references_resolve(name):
    cell = spec.load_cell(name)
    assert spec.module("runners", cell["runner"]).run
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert set(cell["end_to_end"]) <= e2e
    assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for metric, m in cell["per_layer"].items():
        assert spec.module("readers", m["reader"]).read
        assert spec.UNIT.match(m["unit"]), metric
        assert m["source"] in SOURCES
        # a per-layer metric is reported only where the metric it moves is
        assert m["moves"] in cell["end_to_end"], (name, metric)
    if cell["traffic"]["kind"] == "train_batches":
        assert spec.module("trainers", cell["traffic"]["trainer"]).Trainer


@pytest.mark.parametrize("name", spec.names("configs"))
def test_configuration_keeps_every_published_width(name):
    cfg = spec.load("configs", name)
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert len(cfg["source"]) <= 200
    assert spec.resolve(cfg["reference"] + ":logits")


def test_at_most_one_cell_in_four_asks_for_four_chips():
    four = [n for n in CELLS if spec.load("workloads", n)["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


def test_benchmark_json_mirrors_the_directories():
    assert {w["name"] for w in BENCH["workloads"]} == set(CELLS)
    for w in BENCH["workloads"]:
        cell = spec.load("workloads", w["name"])
        assert (w["config"], w["traffic"], w["chips"], w["why"]) == (
            cell["config"], cell["traffic"], cell["chips"], cell["why"])
    assert {c["name"] for c in BENCH["configs"]} == {
        spec.load("workloads", n)["config"] for n in CELLS}
    for c in BENCH["configs"]:
        cfg = spec.load("configs", c["name"])
        assert c["file"] == f"bench_matrix/configs/{c['name']}.json"
        assert (c["source"], c["reduced"]) == (cfg["source"], cfg["reduced"])
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(listed) == set(spec.names("layer_metrics"))
    for key in ("end_to_end", "per_layer"):
        for m in BENCH[key]:
            assert spec.NAME.match(m["name"]) and spec.UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
            where = {n for n in CELLS if m["name"] in spec.load("workloads", n)[key]}
            assert where, f"{m['name']} is reported by no cell"
            assert set(m.get("workloads", CELLS)) == where, m["name"]
    for name, m in listed.items():
        f = spec.load("layer_metrics", name)
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            f["unit"], f["better"], f["source"], f["layer"], f["moves"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] == "host_clock"
    assert BENCH["command"] == ["python3", "-m", "bench_matrix.run"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_a_fifth_cell_is_new_files_only(tmp_path):
    for kind in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(spec.ROOT / kind, tmp_path / kind)
    new = dict(spec.load("workloads", "serve_decode_c32"), why="a later PR's cell")
    (tmp_path / "workloads" / "serve_later.json").write_text(json.dumps(new))
    assert "serve_later" in spec.names("workloads", tmp_path)
    cell = spec.load_cell("serve_later", tmp_path)
    assert cell["traffic_name"] == "chat_closed_c32" and cell["why"] == "a later PR's cell"
    with pytest.raises(spec.SpecError):
        spec.load_cell("no_such_cell", tmp_path)
