"""The `olmo-hybrid-7b-d16` configuration and what came with it: the rule for
a cut on its file, the glue's counts against the published sizes, the
recurrence's roofline count by hand, its reader, and the traffic mix's
lengths."""

import json
from pathlib import Path

import pytest

from bench_matrix import spec, traffic_gen
from bench_matrix.glue import hybrid_linear as glue
from bench_matrix.readers import ReadEnv, recurrence_decode_roofline
from bench_matrix.reduce import scopes, xplane

from _tiny import kept_steps as _kept
from test_bm_specs import check_cut

CFG = spec.load("configs", "olmo-hybrid-7b-d16")
CELL = "serve_olmo_hybrid_reason_c32"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_file_holds_every_published_key_and_cuts_depth_alone():
    check_cut(CFG)
    pub = CFG["published"]
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types"]
    assert CFG["num_hidden_layers"] == 16 and pub["num_hidden_layers"] == 32
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert CFG["layer_types"] == period * 4 and pub["layer_types"] == period * 8
    for key, value in pub.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert CFG["rope_parameters"] == {"rope_theta": None}
    assert [a[:3] for a in CFG["assumed"][:4]] == ["(1)", "(2)", "(3)", "(4)"]
    assert "layers 0-15 of 32" in CFG["deployment"] and "second stage" in CFG["deployment"]
    assert CFG["dtype"]["recurrent_state"] == "float32"
    assert CFG["dtype"]["weights"] == CFG["dtype"]["kv_cache"] == "bfloat16"


def test_the_file_s_published_keys_are_the_catalog_row_s():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Olmo-Hybrid-7B"]
    assert CFG["published"] == row["config"] and CFG["source"] == row["source_url"]


REFUSED = {
    "a_seventeenth_layer_with_a_list_of_sixteen": ({"num_hidden_layers": 17}, "leading"),
    "a_narrower_key": (
        {"linear_key_head_dim": 64, "reduced": CFG["reduced"] + ["linear_key_head_dim"]},
        "must equal"),
    "fewer_linear_heads": ({"linear_num_value_heads": 15}, "exactly the keys that differ"),
    "three_layers": ({"num_hidden_layers": 3, "layer_types": CFG["layer_types"][:3]},
                     "under the floor of 4"),
    "a_theta_written_in": (
        {"rope_parameters": {"rope_theta": 500000},
         "reduced": CFG["reduced"] + ["rope_parameters"]}, "must equal"),
    "a_pattern_of_its_own": (
        {"layer_types": ["full_attention"] * 16}, "leading"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_rule_for_a_cut_refuses(case):
    change, word = REFUSED[case]
    with pytest.raises(AssertionError, match=word):
        check_cut(dict(CFG, **change))


def test_the_glue_counts_the_cut_and_the_published_model():
    """ISSUE 31's arithmetic: a linear layer 215.57 M (mixer 88.75 M), a
    full layer 185.81 M, the mean over a period 208.1 M."""
    d, f = 3840, 11008
    mlp = 3 * d * f
    mixer = glue.layer_params(CFG, 0) - mlp - 2 * d
    assert mixer == (d * 30 * (96 + 96 + 192 + 192) + 30 * 192 * d + 2 * d * 30
                     + 4 * 30 * (96 + 96 + 192) + 2 * 30 + 192)
    assert mixer == pytest.approx(88.75e6, rel=1e-3)
    assert glue.layer_params(CFG, 0) == pytest.approx(215.57e6, rel=1e-4)
    assert glue.layer_params(CFG, 3) == 4 * d * d + mlp + 2 * d + 2 * d
    assert glue.layer_params(CFG, 3) == pytest.approx(185.81e6, rel=1e-4)
    period = sum(glue.layer_params(CFG, i) for i in range(4)) / 4
    assert period == pytest.approx(208.1e6, rel=1e-3)
    assert glue.param_count(CFG) == pytest.approx(4100.7e6, rel=1e-4)
    assert glue.param_count(CFG["published"]) == pytest.approx(32 * 208.13e6 + 770.7e6, rel=1e-3)


def test_training_flops_count_the_recurrence_and_the_attended_keys():
    seq = 4096
    matmuls = sum(glue.layer_params(CFG, i, matmuls_only=True) for i in range(16))
    matmuls += 3840 * 100352
    mixing = 12 * 6 * 30 * 96 * 192 + 4 * 4 * 3840 * (seq + 1) / 2
    assert glue.train_flops_per_token(CFG, seq) == pytest.approx(3 * (2 * matmuls + mixing))
    # a linear layer costs the same at any length: only the four full layers grow
    grow = glue.train_flops_per_token(CFG, 8192) - glue.train_flops_per_token(CFG, seq)
    assert grow == pytest.approx(3 * 4 * 4 * 3840 * 2048)


def test_the_traffic_mix_is_the_cell_the_issue_names():
    t = spec.load("traffic", "reason_closed_c32")
    assert t["arrival"] == {"mode": "closed", "clients": 32, "ramp_seconds": 4.0}
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.9,
                                  "min": 64, "max": 4096}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.5,
                                  "min": 256, "max": 2048}
    assert t["engine"] == {
        "block_size": 16, "pool_blocks": 4096, "prefill_chunk_tokens": 512,
        "max_seq_len": 8192, "min_bucket": 128, "kv_quant": False, "prefix_cache": False,
        "temperature": 0.0, "slots": 32}
    assert (t["strata"], t["shared_prefix_tokens"], t["warmup_seconds"],
            t["trace_seconds"], t["throughput_counts"]) == (64, 0, 6, 3, "generated")
    prompts = traffic_gen.length_cycle(t["prompt_tokens"], t["strata"])
    outputs = traffic_gen.length_cycle(t["output_tokens"], t["strata"])
    assert 64 <= prompts.min() and prompts.max() <= 4096
    assert 256 <= outputs.min() and outputs.max() <= 2048
    assert prompts.max() + outputs.max() <= t["engine"]["max_seq_len"]
    assert 680 < prompts.mean() < 800 and 820 < outputs.mean() < 920
    # a request's mean live context (its prompt and half its output) over 32
    # slots stays far inside the pool's 65 536 tokens: nothing is preempted
    live = 32 * (prompts.mean() + outputs.mean() / 2)
    assert live < 0.65 * t["engine"]["pool_blocks"] * t["engine"]["block_size"]


def test_the_cell_reports_throughput_and_lists_only_what_moves_what_it_reports():
    cell = spec.load_cell(CELL)
    assert cell["config_name"] == "olmo-hybrid-7b-d16" and cell["chips"] == 1
    assert list(cell["end_to_end"]) == ["serve_tokens_per_s", "setup_s"]
    assert {m["moves"] for m in cell["per_layer"].values()} == {"serve_tokens_per_s", "setup_s"}
    new = {"decode_linear_attention_ms", "decode_recurrence_ms",
           "prefill_linear_attention_ms", "prefill_chunk_scan_ms",
           "recurrence_decode_roofline"}
    assert new | {"serve_mfu_pct"} <= set(cell["per_layer"]) and len(cell["per_layer"]) == 14
    assert "paged_decode_roofline" not in cell["per_layer"]
    for name in new - {"recurrence_decode_roofline"}:
        m = cell["per_layer"][name]
        assert m["reader"] == "scope_ms" and m["unit"] == "ms"
    others = [n for n in spec.names("workloads") if n != CELL]
    assert not [n for n in others if new & set(spec.load("workloads", n)["per_layer"])]
    c = cell["correctness"]
    eng = cell["traffic"]["engine"]
    assert (c["prompt_tokens"], c["decode_positions"], c["last_positions"]) == (2048, 8, 256)
    assert c["prompt_tokens"] == 4 * eng["prefill_chunk_tokens"]  # three hand-overs


# --- the roofline count, by hand ----------------------------------------------

def test_recurrence_decode_call_by_hand():
    """Three live rows over the twelve linear layers: each row's state read
    and written in float32, its conv tail read and written, its vectors."""
    got = glue.recurrence_decode_call(CFG, 3, state_itemsize=4, itemsize=2)
    state = 2 * 30 * 96 * 192 * 4
    tail = 2 * 3 * 30 * (96 + 96 + 192) * 2
    vectors = (30 * (96 + 96 + 192) + 2 * 30 + 30 * 192) * 2
    assert state == 4_423_680 and tail == 138_240
    assert got["bytes"] == 3 * 12 * (state + tail + vectors)
    assert got["flops"] == 3 * 12 * (4 * 30 * 96 * 192 + 6 * 30 * 96)
    assert glue.recurrence_decode_call(CFG, 0) == {"bytes": 0.0, "flops": 0.0}
    # memory-bound by three orders of magnitude
    assert got["bytes"] / 819e9 > 100 * got["flops"] / 197e12
    # 32 rows: ISSUE 31's 1.77 GB of state read and written a step
    full = glue.recurrence_decode_call(CFG, 32)
    assert 32 * 12 * state == pytest.approx(1.70e9, rel=1e-2) and full["bytes"] < 1.80e9


def _env(samples, name="no_such_trace_directory", config=CFG):
    said = []
    return ReadEnv(cell={"config": config, "name": name}, samples=samples,
                   trace=xplane.Trace(devices={"/device:TPU:0": []}), peaks=PEAKS,
                   chips=1, memory_peak_bytes=0, say=said.append), said


def _scopes(steps, each_ps=2_000_000):
    dev = "/device:TPU:0"
    return scopes.Scopes(
        ops={dev: [("jit(step)/TransformerLM/layers_0/linear_attn/recurrence/mul", 7,
                    i * 30_000_000, each_ps) for i in range(steps)]},
        runs={dev: [("jit_step", 7, i * 30_000_000, 25_000_000) for i in range(steps)]})


def test_the_reader_pairs_the_runs_of_the_step_with_the_steps_kept(monkeypatch):
    from bench_matrix.readers import scope_time

    args = spec.load("layer_metrics", "recurrence_decode_roofline")["args"]
    kept = [[700, 1200, 90], [701, 1201, 91], [702, 1202]]  # 3, 3 and 2 live rows
    monkeypatch.setattr(scope_time, "_scopes", lambda env: _scopes(3))
    env, said = _env(_kept(kept))
    got = recurrence_decode_roofline.read(args, env)
    need = sum(glue.recurrence_decode_call(CFG, len(s))["bytes"] for s in kept)
    assert got == pytest.approx(100 * (need / 819e9) / (3 * 2e-6))
    assert "memory-bound" in said[-1] and "3 paired" in said[-1]
    # a step whose run is not in the slice (the ledger's `null` of PRs 32-38:
    # one step in flight when the trace stopped): the trailing step goes, both
    # counts are said, and the share is the two pairs'
    monkeypatch.setattr(scope_time, "_scopes", lambda env: _scopes(2))
    env, said = _env(_kept(kept))
    need = sum(glue.recurrence_decode_call(CFG, len(s))["bytes"] for s in kept[:2])
    assert recurrence_decode_roofline.read(args, env) == pytest.approx(
        100 * (need / 819e9) / (2 * 2e-6))
    assert "3 dispatches kept, 2 runs of the program in the slice" in said[-1]
    # no steps kept; a configuration whose glue has no such count
    assert recurrence_decode_roofline.read(args, _env({})[0]) is None
    other = spec.load("configs", "mistral-7b-v0.3-d16")
    env, _ = _env(_kept(kept), config=other)
    assert recurrence_decode_roofline.read(args, env) is None


def test_a_program_without_the_scope_gives_no_number(monkeypatch):
    """The parent commit's programs trace nothing under `recurrence`: the
    metric is left out, not 0, and nothing raises; with no trace file to read
    the same."""
    args = spec.load("layer_metrics", "recurrence_decode_roofline")["args"]
    bare = scopes.Scopes(
        ops={"/device:TPU:0": [("jit(step)/TransformerLM/layers_0/mlp/down_proj/dot_general",
                                7, 0, 1000)]},
        runs={"/device:TPU:0": [("jit_step", 7, 0, 2000)]})
    from bench_matrix.readers import scope_time

    monkeypatch.setattr(scope_time, "_scopes", lambda env: bare)
    assert recurrence_decode_roofline.read(args, _env(_kept([[5]]))[0]) is None
    monkeypatch.undo()
    assert recurrence_decode_roofline.read(args, _env(_kept([[5]]))[0]) is None
    for name in ("decode_linear_attention_ms", "decode_recurrence_ms",
                 "prefill_linear_attention_ms", "prefill_chunk_scan_ms"):
        m = spec.load("layer_metrics", name)
        assert scopes.time_in(bare, m["args"]["program"], m["args"]["scope"]) in (None, 0.0)


def test_the_scope_metrics_read_the_mixer_and_its_two_forms():
    dev = "/device:TPU:0"
    base = "jit(step)/TransformerLM/layers_1/linear_attn/"
    chunk = "jit(prefill_chunk)/TransformerLM/layers_1/linear_attn/"
    sc = scopes.Scopes(
        ops={dev: [(base + "q_proj/dot_general", 7, 0, 1_000_000_000),
                   (base + "recurrence/mul", 7, 2_000_000_000, 3_000_000_000),
                   (base + "gated_norm/mul", 7, 6_000_000_000, 500_000_000),
                   ("jit(step)/TransformerLM/layers_3/attn/cache_attention/x", 7,
                    7_000_000_000, 250_000_000),
                   (chunk + "chunk_scan/dot_general", 9, 10_000_000_000, 4_000_000_000),
                   (chunk + "short_conv/mul", 9, 15_000_000_000, 1_000_000_000)]},
        runs={dev: [("jit_step", 7, 0, 8_000_000_000),
                    ("jit_prefill_chunk", 9, 10_000_000_000, 8_000_000_000)]})
    read = lambda name: scopes.time_in(
        sc, *(spec.load("layer_metrics", name)["args"][k] for k in ("program", "scope")))
    assert read("decode_linear_attention_ms") == pytest.approx(4.5)
    assert read("decode_recurrence_ms") == pytest.approx(3.0)
    assert read("prefill_linear_attention_ms") == pytest.approx(5.0)
    assert read("prefill_chunk_scan_ms") == pytest.approx(4.0)
    assert read("decode_cache_attention_ms") == pytest.approx(0.25)
