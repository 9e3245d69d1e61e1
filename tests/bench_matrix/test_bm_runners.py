"""Both runners end to end at a tiny preset handed in by the test, each in a
fresh process on the suite's virtual CPU devices (four for the FSDP cell);
and the command itself, which must refuse to run off a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent

_RUN = """
import json, sys
sys.path.insert(0, {here!r})
import jax
from _tiny import FAKE_PEAKS, context, tiny_cell
from bench_matrix import run
cell = tiny_cell({name!r}, {arrival!r})
ctx = context(1.0, jax.devices()[:cell["chips"]])
line = run.execute(cell, ctx, FAKE_PEAKS,
                   {{"platform": "cpu", "kind": "cpu", "count": cell["chips"]}})
print(json.dumps(line))
"""

TRAIN = {"train_step_ms", "setup_s"}
SERVE = {"serve_tokens_per_s", "serve_itl_ms_p90", "setup_s"}
# an open loop below what the tiny engine sustains: a later PR's cells
# (PERF.md, Open questions) come as data only if this path works today
OPEN = {"mode": "open", "rate_per_s": 40.0,
        "burst": {"every_s": 0.5, "length_s": 0.1, "factor": 3.0}}
CASES = {
    "lm_train_1chip": ("lm_train_1chip", None, TRAIN),
    "lm_fsdp_4chip": ("lm_fsdp_4chip", None, TRAIN),
    "serve_closed_loop": ("serve_decode_c32", None, SERVE),
    "serve_open_loop": ("serve_decode_c32", OPEN, SERVE),
}


def _in_process(name, arrival, capsys):
    import jax

    from _tiny import FAKE_PEAKS, context, tiny_cell
    from bench_matrix import run

    cell = tiny_cell(name, arrival)
    ctx = context(1.0, jax.devices()[:cell["chips"]])
    try:
        line = run.execute(cell, ctx, FAKE_PEAKS, {
            "platform": "cpu", "kind": "cpu", "count": cell["chips"]})
    finally:
        ctx.compiles.close()
    return line, capsys.readouterr().out


def _fresh_process(name):
    """The DDP adapter brings its own world-1 process group up and down,
    which must not touch the session's."""
    out = subprocess.run(
        [sys.executable, "-c", _RUN.format(here=str(HERE), name=name, arrival=None)],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("case", sorted(CASES))
def test_runner_gives_the_contract_line_at_a_tiny_preset(case, capsys):
    name, arrival, metrics = CASES[case]
    if case == "lm_train_1chip":
        line, said = _fresh_process(name)
    else:
        line, said = _in_process(name, arrival, capsys)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert "correctness:" in said
    if case == "serve_open_loop":
        assert "generator lateness ms p50/max n/a" not in said


def test_command_refuses_to_run_off_tpu():
    out = subprocess.run(
        [sys.executable, "-m", "bench_matrix.run", "--workload", "lm_train_1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    assert not any(l.startswith("{") for l in out.stdout.splitlines())


def test_command_refuses_an_unknown_cell_and_device():
    from bench_matrix import run, spec

    with pytest.raises(spec.SpecError):
        run.main(["--workload", "no_such_cell", "--seed", "1", "--seconds", "1"])
    with pytest.raises(SystemExit, match="not in bench_matrix/peaks.json"):
        run.peaks_for("TPU v99")
    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_inter_token_gaps_map_onto_the_steps_before_completion():
    from bench_matrix.runners.serve import inter_token_s

    step_end = [1.0, 1.1, 1.3, 1.6, 2.0]
    done = [
        # 4 tokens, finished in step 3: first token at 1.05, tokens 2-4 in steps 1-3
        {"tokens": 4, "step": 3, "arrival": 1.0, "ttft_s": 0.05, "requeues": 0},
        {"tokens": 1, "step": 2, "arrival": 1.0, "ttft_s": 0.1, "requeues": 0},
        {"tokens": 3, "step": 4, "arrival": 1.0, "ttft_s": 0.1, "requeues": 1},
    ]
    gaps, skipped = inter_token_s(done, step_end)
    assert skipped == 1
    assert gaps.tolist() == pytest.approx([0.05, 0.2, 0.3])


def test_token_progress_counts_prompts_at_prefill_and_tokens_as_emitted():
    import types

    import numpy as np

    from _tiny import context, tiny_cell
    from bench_matrix.runners.serve import _Loop

    engine = types.SimpleNamespace(cache=types.SimpleNamespace(lengths=np.zeros(2, np.int32)))
    ctx = context(1.0, [])
    ctx.compiles.close()
    loop = _Loop(engine, tiny_cell("serve_decode_c32")["traffic"], 256, ctx)
    for lengths, completed in [
        ([0, 0], None),  # one request prefilling: nothing counted yet
        ([11, 0], None),  # its 10-token prompt done: first token + this step's
        ([12, 0], None),
        ([0, 7], (10, 4)),  # it retires with 4 tokens; a 6-token prompt lands
    ]:
        engine.cache.lengths[:] = lengths
        if completed:
            loop._completed["all"] += sum(completed)
            loop._completed["generated"] += completed[1]
        loop._count()
    assert loop.progress["all"] == [0, 12, 13, 14 + 8]
    assert loop.progress["generated"] == [0, 2, 3, 4 + 2]


def test_decode_probe_keeps_the_keys_of_the_rows_that_decode_and_of_no_other():
    """The runner no longer wraps `engine._step` in it; the program's own tests
    still hold `engine.last_step` against it, so it keeps its meaning."""
    import types

    import numpy as np

    from bench_matrix.runners.serve import _DecodeProbe

    calls = []
    engine = types.SimpleNamespace(
        cache=types.SimpleNamespace(lengths=np.array([5, 16, 0, 99], np.int32)),
        _decoding={3, 1}, _step=lambda *a: calls.append(a) or "out")
    probe = engine._step = _DecodeProbe(engine)
    # slot 0 is mid-prefill (5 tokens landed), slot 2 free: neither decodes.
    # A decoding row attends its cached tokens and the one the step writes.
    assert engine._step("params", "tree") == "out" and calls == [("params", "tree")]
    engine.cache.lengths[[1, 3]] += 1
    engine._decoding = {1}  # slot 3 retired
    engine._step()
    assert probe.keys == [[17, 100], [18]]
    assert len(calls) == 2


def test_the_runner_flushes_on_both_sides_of_the_trace_and_keeps_what_every_call_dispatched(
        monkeypatch, tmp_path):
    """The traced slice holds whole steps only: everything outstanding is read
    back just before the trace starts and again as the last thing inside it
    (`stop_trace` cuts what is in flight). And what the runner keeps of a call
    is the engine's own record of it, `engine.last_step`, call for call: the
    window's chunks and decoding rows (`computed`), the slice's decode steps."""
    import contextlib

    import jax

    from _tiny import context, tiny_cell
    from bench_matrix.runners import serve
    from pytorch_distributed_example_tpu.serve import ServeEngine

    log, records = [], []
    flush, step = ServeEngine.flush, ServeEngine.step

    def counted_flush(self, cause="caller"):
        log.append(("flush", len(self._inflight)))
        return flush(self, cause)

    def recorded_step(self):
        busy = step(self)
        if log and log[0] == "loop":
            records.append((log.count("start"), self.last_step))
        return busy

    @contextlib.contextmanager
    def tracing():
        log.append("start")
        try:
            yield
        finally:
            log.append("stop")

    class Loop(serve._Loop):
        def start(self, horizon_s):
            log[:] = ["loop"]  # the warm-up's and the check's calls are not the loop's
            super().start(horizon_s)

    monkeypatch.setattr(ServeEngine, "flush", counted_flush)
    monkeypatch.setattr(ServeEngine, "step", recorded_step)
    monkeypatch.setattr(serve, "_Loop", Loop)
    cell = tiny_cell("serve_decode_c32")
    ctx = context(1.0, jax.devices()[:1], trace_dir=str(tmp_path))
    ctx.tracing = tracing
    try:
        result = serve.run(cell, ctx)
    finally:
        ctx.compiles.close()
    # one flush before the trace with a call's work in flight, one inside it
    # as its last statement: nothing is outstanding when the trace stops
    marks = [e for e in log if e in ("start", "stop") or e[0] == "flush"]
    assert [m if isinstance(m, str) else m[0] for m in marks] == [
        "flush", "start", "flush", "stop"]
    assert marks[0][1] >= 1 and marks[2][1] >= 1
    assert log[-1] == "stop" and log[-2][0] == "flush"
    # the slice's kept steps are the records of the calls made inside the trace
    traced = [rec for inside, rec in records if inside and rec.decode_keys]
    kept = result.samples["decode_steps"]
    assert len(traced) == len(kept) > 3
    for rec, step_kept in zip(traced, kept):
        assert step_kept["keys"] == list(rec.decode_keys)
        assert step_kept["distinct"] == sum(rec.decode_keys)  # no prefix cache: nothing shared
    # the window's record is every call's between its first and its last step
    computed = result.samples["computed"]
    calls = [rec for inside, rec in records if not inside]
    chunks = [[c[1], c[2]] for rec in calls for c in rec.chunks]
    keys = [k for rec in calls for k in rec.decode_keys]
    n = len(computed["decode_keys"])
    assert 0 < n < len(keys) and n > len(keys) // 2  # the warm-up's calls are not the window's
    assert computed["decode_keys"] == keys[-n:]
    assert computed["chunks"] == chunks[-len(computed["chunks"]):] and computed["chunks"]
    # without a traced slice the runner keeps no step and flushes nothing
    log[:] = []
    ctx = context(0.5, jax.devices()[:1])
    try:
        result = serve.run(cell, ctx)
    finally:
        ctx.compiles.close()
    assert result.samples["decode_steps"] == [] and not [e for e in log if e[0] == "flush"]


def test_numbers_compared_are_repeated_as_the_last_lines_of_standard_error(
        monkeypatch, capsys, tmp_path):
    """A record of a run that is not correct keeps the end of standard
    error: every number compared, beside its limit, is there."""
    import types

    import jax

    from bench_matrix import run, spec
    from bench_matrix.context import Result

    def fake_runner(cell, ctx):
        ctx.say("weights on the device")
        ctx.say_compared("correctness: max_rel 4.1e-02 (limit 0.03)")
        ctx.say_compared("correctness: chosen token within 0.2 (limit 0.03)")
        return Result(correct=False, attempted=3, failed=0, samples={},
                      metrics={"train_step_ms": 1.0, "setup_s": 2.0})

    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                                memory_stats=lambda: {"peak_bytes_in_use": 5})
    monkeypatch.setattr(jax, "devices", lambda: [tpu])
    monkeypatch.setattr(spec, "module", lambda package, name: types.SimpleNamespace(
        run=fake_runner))
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    assert run.main(["--workload", "lm_train_1chip", "--seed", "2147483650",
                     "--seconds", "1", "--trace", "0"]) == 0
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert err.strip().splitlines()[-2:] == [
        "correctness: max_rel 4.1e-02 (limit 0.03)",
        "correctness: chosen token within 0.2 (limit 0.03)"]
