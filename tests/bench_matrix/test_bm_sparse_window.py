"""The `laguna-xs.2-d5` configuration and what came with it: the rule for a
cut on its file, the glue's counts against the published sizes, the two
roofline counts by hand, the readers of the `serve:moe_step` annotations,
and the traffic mix's lengths."""

import copy
import json

import numpy as np
import pytest

from bench_matrix import flops, spec, traffic_gen
from bench_matrix.glue import sparse_window as glue
from bench_matrix.readers import ReadEnv, moe_decode_roofline, moe_steps, window_decode_roofline
from bench_matrix.reduce import scopes, xplane

from _tiny import kept_steps as _kept
from test_bm_specs import check_cut

CFG = spec.load("configs", "laguna-xs.2-d5")
CELL = "serve_laguna_mixed_c32"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_file_holds_every_published_key_and_cuts_depth_alone():
    pub = CFG["published"]
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types",
                              "num_attention_heads_per_layer"]
    assert CFG["num_hidden_layers"] == 5 and pub["num_hidden_layers"] == 40
    assert CFG["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert CFG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert CFG["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    for key, value in pub.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert len(CFG["assumed"]) >= 4 and "pipeline stages" in CFG["deployment"]
    assert CFG["dtype"]["router"] == "float32" and CFG["dtype"]["logits"] == "float32"


REFUSED = {
    "a_sixth_layer_with_lists_of_five": ({"num_hidden_layers": 6}, "leading"),
    "the_window_changed": ({"sliding_window": 256}, "exactly the keys that differ"),
    "the_window_changed_and_listed": (
        {"sliding_window": 256, "reduced": CFG["reduced"] + ["sliding_window"]}, "must equal"),
    "fewer_experts_a_token": (
        {"num_experts_per_tok": 4, "reduced": CFG["reduced"] + ["num_experts_per_tok"]},
        "must equal"),
    "four_layers": (
        {"num_hidden_layers": 4, **{k: CFG[k][:4] for k in CFG["reduced"][1:]}},
        "under the floor of 5"),
    "a_narrower_expert": (
        {"moe_intermediate_size": 256, "reduced": CFG["reduced"] + ["moe_intermediate_size"]},
        "must equal"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_rule_for_a_cut_refuses(case):
    change, word = REFUSED[case]
    with pytest.raises(AssertionError, match=word):
        check_cut(dict(CFG, **change))


def test_the_glue_counts_the_cut_and_the_published_model():
    assert glue.param_count(CFG) == pytest.approx(3.870e9, rel=1e-3)
    assert glue.param_count(CFG["published"]) == pytest.approx(33.44e9, rel=1e-3)
    # a full layer's and a window layer's attention, a sparse MLP (the issue's arithmetic)
    d = 2048
    assert glue.layer_params(CFG, 0) == 29_458_432 + 3 * d * 8192
    assert glue.layer_params(CFG, 1) - glue.layer_params(CFG, 4) == 37_879_808 - 29_458_432
    assert glue.layer_params(CFG, 1) - 37_879_808 == 256 * 3 * d * 512 + 3 * d * 512 + d * 256
    assert glue.layer_params(CFG, 1, active=True) - 37_879_808 == (8 + 1) * 3 * d * 512 + d * 256


def test_training_flops_count_active_parameters_and_windowed_keys():
    seq = 4096
    active = sum(glue.layer_params(CFG, i, active=True) for i in range(5)) + 2048 * 100352
    keys_full, keys_window = (seq + 1) / 2, (512 * 513 / 2 + (seq - 512) * 512) / seq
    attention = 4 * 128 * (2 * 48 * keys_full + 3 * 64 * keys_window)
    assert glue.train_flops_per_token(CFG, seq) == pytest.approx(3 * (2 * active + attention))
    assert glue.train_flops_per_token(CFG, seq) < 0.3 * 6 * glue.param_count(CFG)
    # inside the window a window layer is a full layer
    short = glue.train_flops_per_token(CFG, 256)
    assert short == pytest.approx(3 * (2 * active + 4 * 128 * 288 * 257 / 2))


def test_the_traffic_mix_stays_inside_the_engine_s_sequence_length():
    t = spec.load("traffic", "code_mixed_closed_c32")
    prompts = traffic_gen.length_cycle(t["prompt_tokens"], t["strata"])
    outputs = traffic_gen.length_cycle(t["output_tokens"], t["strata"])
    assert prompts.min() >= 128 and prompts.max() <= 7424
    assert outputs.min() >= 64 and outputs.max() <= 768
    assert prompts.max() + outputs.max() <= t["engine"]["max_seq_len"] == 8192
    assert 2700 < prompts.mean() < 2900 and 280 < outputs.mean() < 320
    eng = t["engine"]
    # every slot can reach max_seq_len in the full layers' pool: nothing is preempted
    assert eng["pool_blocks"] * eng["block_size"] == eng["slots"] * eng["max_seq_len"]
    assert not eng["prefix_cache"] and not eng["kv_quant"]
    cell = spec.load_cell(CELL)
    assert cell["correctness"]["prompt_tokens"] == 4 * CFG["sliding_window"]
    assert "paged_decode_roofline" not in cell["per_layer"]


def test_the_cell_reports_throughput_and_lists_only_what_moves_what_it_reports():
    """`serve_itl_ms_p90` is not this cell's: over six seeds it spread 0.87 %
    (`PERF.md` section 6) where a new cell is admitted under half its 1.5 %
    bound, and the metrics that move it go with it."""
    cell = spec.load_cell(CELL)
    assert list(cell["end_to_end"]) == ["serve_tokens_per_s", "setup_s"]
    moved = {m["moves"] for m in cell["per_layer"].values()}
    assert moved == {"serve_tokens_per_s", "setup_s"}
    assert {"decode_moe_ms", "prefill_moe_ms", "decode_window_attention_ms",
            "moe_experts_hit_mean", "moe_decode_roofline",
            "window_decode_roofline", "serve_mfu_pct"} <= set(cell["per_layer"])
    assert len(cell["per_layer"]) == 15


def test_the_check_s_replay_has_the_shapes_of_every_engine_that_serves_the_configuration():
    """The reference asks the glue which experts the system chose, and the
    glue replays the prefill in the shapes `model.check.replay` gives: they
    are the shapes of the engine of every cell that runs the configuration,
    and the check's prompt is whole chunks, so every compared position is
    told."""
    check = CFG["model"]["check"]
    assert check["routing"] == "system" and 0 < check["tie_margin"] < 1 / CFG["num_experts"]
    cells = [spec.load_cell(n) for n in spec.names("workloads")]
    mine = [c for c in cells if c["config_name"] == "laguna-xs.2-d5"]
    assert [c["name"] for c in mine] == [CELL]
    for cell in mine:
        eng = cell["traffic"]["engine"]
        assert check["replay"] == {k: eng[k] for k in
                                   ("block_size", "prefill_chunk_tokens", "max_seq_len")}
        assert cell["correctness"]["prompt_tokens"] % eng["prefill_chunk_tokens"] == 0
        assert cell["correctness"]["last_positions"] <= eng["prefill_chunk_tokens"]


# --- the two roofline counts, by hand ---------------------------------------

def test_window_decode_call_by_hand():
    """Three rows (one inside the window, two past it) over two full layers
    of 48 heads and three window layers of 64: a window layer reads at most
    512 keys of a row, a parked row is not in the list."""
    keys = [100, 512, 3000]
    got = glue.window_decode_call(CFG, keys, itemsize=2)
    full, window = 100 + 512 + 3000, 100 + 512 + 512
    per_key = 8 * 128 * 2 * 2  # K and V of 8 KV heads, bfloat16
    assert got["bytes"] == (2 * full + 3 * window) * per_key
    assert got["flops"] == 4.0 * 128 * (2 * full * 48 + 3 * window * 64)
    assert glue.window_decode_call(CFG, [], 2) == {"bytes": 0.0, "flops": 0.0}
    # all full layers: what `flops.paged_decode_call` counts for that model
    dense = dict(CFG, layer_types=["full_attention"] * 5,
                 num_attention_heads_per_layer=[48] * 5)
    one = flops.paged_decode_call(keys, 48, 8, 128, 2)
    assert glue.window_decode_call(dense, keys, 2) == {
        "bytes": 5 * one["bytes"], "flops": 5 * one["flops"]}


def test_moe_decode_call_by_hand():
    """Three live rows, four sparse layers, 24 assignments a layer, 20 to 23
    distinct experts: the weights of the experts hit, once, and each layer's
    shared expert and router."""
    expert = 3 * 2048 * 512
    hit = [20, 23, 22, 21]
    got = glue.moe_decode_call(CFG, rows=3, assignments=4 * 24, experts_hit=hit, itemsize=2)
    assert got["bytes"] == 2 * (sum(hit) * expert + 4 * (expert + 2048 * 256))
    assert got["flops"] == 2.0 * (96 * expert + 3 * 4 * (expert + 2048 * 256))
    # no live row: nothing routed; the shared expert and router are still read
    idle = glue.moe_decode_call(CFG, 0, 0, [0, 0, 0, 0], 2)
    assert idle["flops"] == 0 and idle["bytes"] == 2 * 4 * (expert + 2048 * 256)


def _trace(kernel_calls, each_ns=1000):
    ops = [("paged_decode_attention.1 custom-call bf16[32,48,128] tpu_custom_call",
            i * 5000, i * 5000 + each_ns) for i in range(kernel_calls)]
    return xplane.Trace(devices={"/device:TPU:0": ops})


def _env(trace, samples):
    said = []
    cell = {"config": CFG, "name": "no_such_trace_directory"}
    return ReadEnv(cell=cell, samples=samples, trace=trace, peaks=PEAKS, chips=1,
                   memory_peak_bytes=0, say=said.append), said


def _step_runs(n, path, calls, each_ps=1_000_000):
    """`n` runs of `jit_step`, each with `calls` operations at `path`."""
    gap = 100_000_000
    return scopes.Scopes(
        ops={"/device:TPU:0": [(path.format(k), 7, i * gap + 1000 + k * 5_000_000, each_ps)
                               for i in range(n) for k in range(calls)]},
        runs={"/device:TPU:0": [("jit_step", 7, i * gap, gap - 1_000_000) for i in range(n)]})


def test_window_decode_roofline_reads_runs_that_pair_with_steps(monkeypatch):
    from bench_matrix.readers import scope_time

    args = spec.load("layer_metrics", "window_decode_roofline")["args"]
    steps = [[100, 512, 3000], [101, 513, 3001]]
    # the window layers' calls sit under `window_attention/cache_attention`
    path = "jit(step)/TransformerLM/layers_{}/attn/window_attention/cache_attention/pallas_call"
    monkeypatch.setattr(scope_time, "_scopes", lambda env: _step_runs(2, path, 5))
    env, said = _env(_trace(0), _kept(steps))
    got = window_decode_roofline.read(args, env)
    need = sum(glue.window_decode_call(CFG, s, 2)["bytes"] for s in steps)
    assert got == pytest.approx(100 * (need / 819e9) / (10 * 1e-6))
    assert "windowed decode attention" in said[0] and "2 paired" in said[0]
    # one run more than steps kept: the leading run goes, both counts are said
    monkeypatch.setattr(scope_time, "_scopes", lambda env: _step_runs(3, path, 5))
    env, said = _env(_trace(0), _kept(steps))
    assert window_decode_roofline.read(args, env) == pytest.approx(got)
    assert "2 dispatches kept, 3 runs of the program in the slice" in said[0]
    # no traced slice, no steps kept, a model whose glue has no such count
    assert window_decode_roofline.read(args, _env(_trace(0), {})[0]) is None
    assert window_decode_roofline.read(args, _env(_trace(0), {"decode_steps": []})[0]) is None
    monkeypatch.setattr(scope_time, "_scopes", lambda env: None)
    assert window_decode_roofline.read(args, _env(None, _kept(steps))[0]) is None
    env, _ = _env(_trace(0), _kept(steps))
    env.cell = {"config": spec.load("configs", "mistral-7b-v0.3-d16"), "name": "x"}
    assert window_decode_roofline.read(args, env) is None


def test_the_moe_readers_give_nothing_without_a_trace_or_annotations():
    hit = spec.load("layer_metrics", "moe_experts_hit_mean")["args"]
    roof = spec.load("layer_metrics", "moe_decode_roofline")["args"]
    for trace in (None, _trace(10)):  # no slice; a slice whose file is not there
        env, _ = _env(trace, _kept([[5]]))
        assert moe_steps.read(hit, env) is None
        assert moe_decode_roofline.read(roof, env) is None


def test_the_annotations_of_a_traced_engine_pair_with_its_steps(tmp_path, monkeypatch):
    """A tiny engine's decode steps traced on the CPU: one `serve:moe_step`
    a step on the host line, with the counters the engine recorded; the
    roofline reader pairs them with the runs of `jit_step` and the steps the
    runner would have kept, and refuses when one is missing."""
    import jax

    from bench_matrix import modelglue, run
    from pytorch_distributed_example_tpu.serve import ServeEngine

    small = dict(
        CFG, hidden_size=64, head_dim=16, num_attention_heads=6, num_key_value_heads=2,
        num_attention_heads_per_layer=[6, 8, 8, 8, 6], intermediate_size=96, vocab_size=128,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, sliding_window=8,
        dtype={"weights": "float32", "activations": "float32", "kv_cache": "float32"})
    model = modelglue.build_model(small, 64, remat=False)
    engine = ServeEngine(model, modelglue.make_variables(model, small, 3), slots=2,
                         block_size=4, pool_blocks=32, prefill_chunk_tokens=8, min_bucket=4)
    engine.submit(np.arange(9, dtype=np.int32), 12, rid="a")
    for _ in range(4):
        engine.step()  # prefill and the first decode steps, untraced
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    trace_dir = tmp_path / "trace" / "tiny"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    # as `runners/serve.py::run` does: everything read back before the trace
    # starts and before it stops, so a step's annotation (written when it is
    # READ BACK, a call after its dispatch) lies in the slice with its run
    recorded, kept = [], []
    engine.flush()
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        for i in range(5):
            engine.step()
            kept.append(list(engine.last_step.decode_keys))
            if i:  # the call read the step before it back
                recorded.append((engine.metrics.moe_assignments,
                                 list(engine.metrics.moe_experts_hit)))
        engine.flush()
        recorded.append((engine.metrics.moe_assignments, list(engine.metrics.moe_experts_hit)))
    finally:
        jax.profiler.stop_trace()
    assert all(len(k) == 1 for k in kept)  # every call of the slice dispatched a step
    env, said = _env(xplane.Trace(devices={"cpu": []}), _kept(kept))
    env.cell = {"config": small, "name": "tiny"}
    got = moe_steps.steps(env)
    assert [(s["assignments"], s["experts_hit"]) for s in got] == recorded
    assert all(s["rows"] == 1 and s["assignments"] == 2 * 4 for s in got)
    mean = moe_steps.read({"stat": "experts_hit_mean"}, env)
    assert mean == pytest.approx(np.mean([np.mean(h) for _, h in recorded]))
    # a CPU trace has no device plane: the roofline reader has no time to read
    assert moe_decode_roofline.read(
        spec.load("layer_metrics", "moe_decode_roofline")["args"], env) is None
    # with the time of five runs handed to it, it pairs steps and counts
    from bench_matrix.readers import scope_time

    path = "jit(step)/mlp/moe/experts/ragged_dot"
    monkeypatch.setattr(scope_time, "_scopes", lambda env: _step_runs(5, path, 1, 2_000_000))
    args = spec.load("layer_metrics", "moe_decode_roofline")["args"]
    share = moe_decode_roofline.read(args, env)
    need = sum(glue.moe_decode_call(small, 1, a, h, 4)["bytes"] for a, h in recorded)
    assert share == pytest.approx(100 * (need / 819e9) / (5 * 2e-6))
    assert "5 paired" in said[-2] and "experts hit a layer" in said[-1]
    # a run fewer than annotated steps (the last one cut): the trailing
    # annotation goes, both counts are said, the share is the four pairs'
    monkeypatch.setattr(scope_time, "_scopes", lambda env: _step_runs(4, path, 1, 2_000_000))
    share = moe_decode_roofline.read(args, env)
    need = sum(glue.moe_decode_call(small, 1, a, h, 4)["bytes"] for a, h in recorded[:4])
    assert share == pytest.approx(100 * (need / 819e9) / (4 * 2e-6))
    assert "5 dispatches kept, 4 runs of the program in the slice" in said[-2]
