"""Device time by program component: the scope-path reduction on the trace
recorded on the chip and on hand-built cases, and the reader over it."""

import importlib.util
import json
import os
import shutil
from pathlib import Path

import pytest

from bench_matrix import run, spec
from bench_matrix.readers import ReadEnv
from bench_matrix.reduce import scopes, xplane

FIXTURE = Path(xplane.__file__).resolve().parents[1] / "fixtures" / "v5e_small.xplane.pb"
WANT = json.loads((FIXTURE.parent / "v5e_small.json").read_text())
DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    return scopes.read(str(FIXTURE))


# read by hand from the file with tensorflow's generated classes when this
# test was written: 12 operations a run, three of them Mosaic calls of
# 17.48 + 23.76 + 15.89 us; a run's event on the modules line is 0.1558 ms
ON_THE_FIXTURE = {
    "ops": lambda sc: len(sc.ops[DEV]) == 24,
    "runs": lambda sc: [(r[0], r[1]) for r in sc.runs[DEV]] == [
        ("jit_work", 11666716634938699452)] * 2,
    "every_op_in_the_program": lambda sc: {o[1] for o in sc.ops[DEV]} == {
        11666716634938699452},
    "forward_mosaic_path": lambda sc: sc.ops[DEV][2][0] == "jit(work)/jvp()/pallas_call",
    "backward_mosaic_path": lambda sc: sc.ops[DEV][6][0] == (
        "jit(work)/transpose(jvp())/pallas_call"),
    "no_path": lambda sc: sc.ops[DEV][0][0] == "",
    "picoseconds": lambda sc: sc.ops[DEV][2][2:] == (45259020000, 17476328),
    "pallas_ms_a_run": lambda sc: scopes.time_in(sc, "^jit_work$", "pallas_call")
    == pytest.approx(0.0571, abs=5e-5),
    # the same six events `xplane.time_matching` finds by the kernel's name
    "pallas_is_kernel_s": lambda sc: scopes.time_in(sc, "^jit_work$", "pallas_call")
    == pytest.approx(1e3 * WANT["kernel_s"] / 2, rel=1e-4),
    # the pattern sees the path with the wrappers peeled, not the wrappers
    "wrappers_peeled": lambda sc: scopes.time_in(sc, "^jit_work$", "^pallas_call$")
    == scopes.time_in(sc, "^jit_work$", "pallas_call")
    and scopes.time_in(sc, "^jit_work$", "transpose|jvp|jit") is None,
    "whole_program_ms_a_run": lambda sc: scopes.time_in(sc, "^jit_work$")
    == pytest.approx(0.1556, abs=5e-5),
    "no_such_program": lambda sc: scopes.time_in(sc, "^jit_step$") is None,
    "no_such_scope": lambda sc: scopes.time_in(sc, "^jit_work$", "kv_gather") is None,
    "all_unscoped": lambda sc: scopes.unscoped_share(sc) == 1.0,
}


@pytest.mark.parametrize("what", sorted(ON_THE_FIXTURE))
def test_scopes_on_the_trace_recorded_on_the_chip(what, recorded):
    assert ON_THE_FIXTURE[what](recorded)


def test_table_on_the_fixture_names_the_program_and_agrees_with_the_modules_line(recorded):
    text = scopes.table(str(FIXTURE))
    assert "program jit_work: 2 runs, 0.156 ms a run in operations (0.156 ms by" in text
    assert "forward" in text and "backward" in text  # the program has a backward pass
    assert text.splitlines()[-1] == "unscoped: 100.00 % of device busy time"
    p = scopes.by_component(recorded)["jit_work"]
    assert p["ms"] < p["module_ms"] < 1.005 * p["ms"]  # shorter by the seams only


# what the readers over `xplane.load` gave on the fixture before this module
# existed, from the numbers recorded with it: bit for bit
BEFORE = {
    "trace_idle": ({}, 100.0 * (1.0 - WANT["busy_s"] / WANT["window_s"])),
    "trace_share": ({"pattern": WANT["kernel_pattern"]},
                    100.0 * (WANT["kernel_s"] / WANT["busy_s"])),
    "span_host": ({"span": "step dispatch"}, 1e3 * WANT["idle_per_dispatch_s"]),
    "trace_collective": ({"which": "total"}, 0.0),
}


@pytest.mark.parametrize("reader", sorted(BEFORE))
def test_readers_over_xplane_load_read_the_fixture_as_before(reader):
    args, want = BEFORE[reader]
    env = ReadEnv(cell={}, samples={}, trace=xplane.load(str(FIXTURE)), peaks={},
                  chips=1, memory_peak_bytes=0, say=lambda s: None)
    assert spec.module("readers", reader).read(args, env) == want


PATHS = {
    # path: (component, component at depth 2, phase)
    "jit(step)/transpose(jvp(TransformerLM))/layers_7/mlp/gate_proj/dot_general":
        ("mlp", "mlp/gate_proj", scopes.BACKWARD),
    "checkpoint/rematted_computation/layers_0/attn/flash_attention/pallas_call":
        ("attn", "attn/flash_attention", scopes.RECOMPUTED),
    "jit(step)/mul": ("unscoped", "unscoped", scopes.FORWARD),
    # recomputed forward work sits under the backward pass's wrapper
    "jit(local_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/"
    "rematted_computation/layers_1/mlp/up_proj/dot_general":
        ("mlp", "mlp/up_proj", scopes.RECOMPUTED),
    "jit(local_step)/transpose(jvp(TransformerLM))/jvp(TransformerLM)/checkpoint/"
    "layers_1/attn/q_proj/transpose": ("attn", "attn/q_proj", scopes.BACKWARD),
    # Flax's name for a method other than __call__ is not a component
    "jit(step)/TransformerLM/layers_0/attn/attn._decode/attn._decode_paged/kv_gather/gather":
        ("attn", "attn/kv_gather", scopes.FORWARD),
    "jit(local_step)/transpose(jvp(loss))/add_any": ("loss", "loss", scopes.BACKWARD),
    "jit(local_step)/jvp(loss)/jit(_take)/gather": ("loss", "loss", scopes.FORWARD),
    "jit(local_step)/transpose(jvp(jit(_take)))": ("unscoped", "unscoped", scopes.BACKWARD),
    "jit(step)/shard_map/optimizer/sqrt": ("optimizer", "optimizer", scopes.FORWARD),
    "jit(work)/jvp()/pallas_call": ("unscoped", "unscoped", scopes.FORWARD),
    "": ("unscoped", "unscoped", scopes.FORWARD),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_wrappers_are_cut_and_the_phase_is_read_from_them(path):
    comp, comp2, phase = PATHS[path]
    assert scopes.component(path) == comp
    assert scopes.component(path, 2) == comp2
    assert scopes.names(path)[1] == phase


MS = 10**9  # ps


def _two_devices():
    """Two programs on device 0 (two runs of `jit_step`, one of
    `jit_prefill_chunk` between them), one run of `jit_step` on device 1,
    and on device 0 an operation whose run the edge of the trace cut off."""
    step, chunk = 11, 22
    sc = scopes.Scopes()
    sc.runs["/device:TPU:0"] = [
        ("jit_step", step, 0, 10 * MS), ("jit_prefill_chunk", chunk, 10 * MS, 5 * MS),
        ("jit_step", step, 20 * MS, 10 * MS)]
    sc.ops["/device:TPU:0"] = [
        ("jit(step)/TransformerLM/layers_0/attn/attn._decode_paged/kv_gather/gather", step, 0, 4 * MS),
        ("jit(step)/TransformerLM/layers_0/mlp/up_proj/dot_general", step, 4 * MS, 2 * MS),
        ("jit(prefill_chunk)/TransformerLM/layers_0/attn/attn._decode_paged/kv_gather/gather",
         chunk, 10 * MS, 3 * MS),
        ("jit(step)/TransformerLM/layers_0/attn/attn._decode_paged/kv_gather/gather", step, 20 * MS, 6 * MS),
        ("jit(step)/sample/argmax", step, 26 * MS, 1 * MS),
        ("jit(step)/mul", step, 40 * MS, 7 * MS),  # after the last run
    ]
    sc.runs["/device:TPU:1"] = [("jit_step", step, 0, 10 * MS)]
    sc.ops["/device:TPU:1"] = [
        ("jit(step)/TransformerLM/layers_0/attn/attn._decode_paged/kv_gather/gather", step, 0, 2 * MS)]
    return sc


GATHER = "(^|/)kv_gather(/|$)"
BY_HAND = {
    # device 0: (4 + 6) / 2 runs = 5; device 1: 2 / 1; mean 3.5
    "gather_in_step": (("^jit_step$", GATHER), 3.5),
    "gather_in_chunk": (("^jit_prefill_chunk$", GATHER), 3.0),  # device 0 alone ran it
    # device 0: (4 + 2 + 6 + 1) / 2 = 6.5, the stray operation left out; device 1: 2
    "whole_step": (("^jit_step$", None), 4.25),
    "both_programs": (("^jit_", GATHER), (13 / 3 + 2) / 2),
    "segment_not_substring": (("^jit_step$", "(^|/)gather(/|$)"), 3.5),  # the primitive
    "no_match": (("^jit_step$", "(^|/)kv(/|$)"), None),
}


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_time_is_per_run_of_the_program_and_a_mean_over_devices(case):
    (program, scope), want = BY_HAND[case]
    got = scopes.time_in(_two_devices(), program, scope)
    assert got is None if want is None else got == pytest.approx(want)


def test_components_per_program_and_what_no_run_holds():
    sc = _two_devices()
    progs = scopes.by_component(sc)
    assert progs["jit_step"]["runs"] == 1  # 3 runs over 2 devices
    assert progs["jit_step"]["ms"] == pytest.approx(15 / 3)
    assert progs["jit_step"]["components"]["attn/kv_gather"][scopes.FORWARD] == pytest.approx(12 / 3)
    assert progs["jit_prefill_chunk"]["components"] == {
        "attn/kv_gather": {scopes.FORWARD: pytest.approx(3.0)}}
    assert progs[None]["ms"] == pytest.approx(7 / 2)
    assert scopes.unscoped_share(sc) == pytest.approx(7 / 25)
    text = scopes.table(sc)
    assert "operations in no run of their program: 3.500 ms" in text
    assert "unscoped: 28.00 % of device busy time" in text
    assert "forward" not in text  # no program here has a backward pass


def test_reader_finds_the_trace_of_the_run_parses_once_and_says_the_table_once(
        tmp_path, monkeypatch):
    reader = spec.module("readers", "scope_time")
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(reader, "_PARSED", {})
    said = []
    env = ReadEnv(cell={"name": "some_cell"}, samples={}, trace=xplane.load(str(FIXTURE)),
                  peaks={}, chips=1, memory_peak_bytes=0, say=said.append)
    args = {"program": "^jit_work$", "scope": "pallas_call"}
    assert reader.read(args, env) is None and not said  # no directory: no number, no error
    where = tmp_path / "trace" / "some_cell" / "plugins" / "profile" / "2026_09_27"
    os.makedirs(where)
    assert reader.read(args, env) is None  # a directory and no file
    shutil.copy(FIXTURE, where / "host.xplane.pb")
    parses = []
    monkeypatch.setattr(scopes, "read", lambda p, real=scopes.read: parses.append(p) or real(p))
    assert reader.read(args, env) == pytest.approx(0.0571, abs=5e-5)
    assert reader.read({"program": "^jit_work$", "scope": None}, env) == pytest.approx(
        0.1556, abs=5e-5)
    assert reader.read({"program": "^jit_work$"}, env) == pytest.approx(0.1556, abs=5e-5)
    assert len(parses) == 1 and len(said) == 1
    assert said[0].startswith("device time by program and component (")
    assert "\nprogram jit_work: 2 runs" in said[0]
    env.trace = None
    assert reader.read(args, env) is None  # not a traced run


# The nine metrics over the scopes wait in `layer_metrics_queued/`: the harness
# reports in a cell what the cell's own file lists, and a PR that changes the
# program may not edit that file. A `benchmark` PR moves each file to
# `layer_metrics/` and appends its name to the cells of its kind.
QUEUED = "layer_metrics_queued"
NEW_METRICS = spec.names(QUEUED)
CELLS_OF = {"train": ["lm_fsdp_4chip", "lm_train_1chip"],
            "decode": ["serve_decode_c32", "serve_prefill_c8"],
            "prefill": ["serve_decode_c32", "serve_prefill_c8"]}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_scope_metrics_are_times_of_the_device_trace_and_never_shares(name):
    import re

    m = spec.load(QUEUED, name)
    assert (m["unit"], m["better"], m["source"], m["reader"]) == (
        "ms", "lower", "device_trace", "scope_time")
    re.compile(m["args"]["program"])
    if m["args"]["scope"] is not None:
        re.compile(m["args"]["scope"])
    # ready to be listed: every cell of its kind reports the metric it moves,
    # its layer is one BENCHMARK.json names, and no accepted metric has its name
    for cell in CELLS_OF[name.split("_")[0]]:
        assert m["moves"] in spec.load("workloads", cell)["end_to_end"], (name, cell)
    bench = json.loads((Path(spec.ROOT).parent / "BENCHMARK.json").read_text())
    assert m["layer"] in {x["layer"] for x in bench["per_layer"]}
    assert name not in spec.names("layer_metrics")


def test_nine_metrics_read_the_scopes():
    assert len(NEW_METRICS) == 9


def test_command_prints_the_table_and_the_queued_metrics_that_find_something(
        capsys, monkeypatch):
    assert scopes.main([str(FIXTURE)]) == 0  # a file; `jit_work` is no metric's program
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("program jit_work: 2 runs") and out[-1].startswith("unscoped:")
    monkeypatch.setattr(scopes, "read", lambda path: _two_devices())
    # a directory: the newest trace in it; a serve cell's metrics by their names
    assert scopes.main([str(FIXTURE.parent), "^(decode|prefill)_"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert "decode_kv_gather_ms 3.5 ms" in lines and "decode_step_device_ms 4.25 ms" in lines
    assert not [ln for ln in lines if ln.startswith("train_")]  # nothing to read: left out


def test_field_numbers_are_those_of_the_installed_xplane_proto():
    """Against tensorflow's generated descriptor where this installation has
    one; the module itself never imports it."""
    found = importlib.util.find_spec("tensorflow")
    if found is None or not found.origin:
        pytest.skip("no tensorflow here to check the field numbers against")
    path = Path(found.origin).parent / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py"
    if not path.is_file():
        pytest.skip(f"{path} is not there")
    pytest.importorskip("google.protobuf")
    module_spec = importlib.util.spec_from_file_location("_xplane_pb2_for_test", path)
    pb2 = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(pb2)  # needs google.protobuf only
    for message, fields in scopes.FIELDS.items():
        numbers = {f.name: f.number for f in getattr(pb2, message).DESCRIPTOR.fields}
        for name, number in fields.items():
            assert numbers[name] == number, (message, name)


def test_the_benchmark_imports_no_profiler_package():
    import ast

    root = Path(spec.ROOT)
    for path in root.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            names_ = []
            if isinstance(node, ast.Import):
                names_ = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
                names_ = [node.module]
            for n in names_:
                assert n.split(".")[0] not in ("tensorflow", "xprof", "tensorboard",
                                               "tensorboard_plugin_profile"), (path, n)
