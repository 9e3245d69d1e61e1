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
