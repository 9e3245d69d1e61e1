"""The `solar-open2-250b-d4-e40` configuration and what came with it: the
rule for a cut on its file, the glue's counts against the published sizes,
the roofline counts by hand, the new reader's pairing of whole runs with
annotated chunks, the traffic mix, the check's replay, and the cell end to end
at a tiny preset."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench_matrix import modelglue, spec, traffic_gen
from bench_matrix.glue import kda_moe as glue
from bench_matrix.readers import (
    ReadEnv, chunk_scan_roofline, latent_steps, moe_decode_roofline,
    recurrence_decode_roofline, serve_mfu,
)
from bench_matrix.reduce import scopes, xplane

from test_bm_specs import check_cut, depth_floor

NAME, CELL, MIX = "solar-open2-250b-d4-e40", "serve_solar_longctx_c16", "longctx_mixed_closed_c16"
CFG = spec.load("configs", NAME)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
NEW_METRICS = ("prefill_kda_gate_ms", "chunk_scan_roofline")
# the linear kind's five under this PR's own names: the benchmark's own test
# holds the accepted five to the Olmo cell alone (below), and the cell exists
# for the scan and the recurrence
KDA_METRICS = {
    "kda_decode_linear_attention_ms": "decode_linear_attention_ms",
    "kda_prefill_linear_attention_ms": "prefill_linear_attention_ms",
    "kda_decode_recurrence_ms": "decode_recurrence_ms",
    "kda_prefill_chunk_scan_ms": "prefill_chunk_scan_ms",
    "kda_recurrence_decode_roofline": "recurrence_decode_roofline",
}


# --- the file ----------------------------------------------------------------

def test_the_file_holds_every_published_key_and_cuts_depth_experts_and_vocabulary():
    check_cut(CFG)
    pub = CFG["published"]
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"], CFG["vocab_size"]) == (4, 40, 24576)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"], pub["vocab_size"]) == (
        48, 320, 196608)
    for key, value in pub.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    # the list of softmax layers is no list of one entry a layer: it stays whole
    assert CFG["gqa_layers"] == list(range(0, 48, 4)) and depth_floor(pub) == 4
    assert [glue.is_linear(CFG, i) for i in range(4)] == [False, True, True, True]
    assert (CFG["hidden_size"], CFG["num_attention_heads"], CFG["num_key_value_heads"],
            CFG["head_dim"], CFG["moe_intermediate_size"], CFG["num_experts_per_tok"],
            CFG["n_shared_experts"]) == (4096, 64, 8, 128, 1280, 8, 1)
    assert CFG["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None}
    assert not CFG["use_rope"] and CFG["use_gqa_gate"] and CFG["kda_allow_neg_eigval"]
    assert not CFG["kda_use_full_proj"] and CFG["first_k_dense_replace"] == 0
    assert [a[:3] for a in CFG["assumed"][:8]] == [f"({i})" for i in range(1, 9)]
    assert CFG["deployment"].startswith("each layer is divided over 8 chips")
    for words in ("experts 0-39", "24576 rows", "12 pipeline stages", "96 chips"):
        assert words in CFG["deployment"], words
    for words in ("depth_floor reads 4", "3308 M", "6.62 GB", "250.3 B", "137.7 M", "109.1 M",
                  "2.15 GB", "0.21 GB", "56 %", "What the cut distorts", "1/8 of the tokens",
                  "12 x a 48-layer"):
        assert words in CFG["reduction_notes"], words
    assert CFG["dtype"] == {
        "weights": "bfloat16", "activations": "bfloat16", "logits": "float32",
        "router": "float32", "kv_cache": "bfloat16", "recurrent_state": "float32",
        "conv_tail": "bfloat16"}
    assert len(CFG["why"]) <= 200 and len(CFG["source"]) <= 200


def test_the_file_s_published_keys_are_the_catalog_row_s():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Solar-Open2-250B"]
    assert CFG["published"] == row["config"] and CFG["source"] == row["source_url"]


def test_the_assumptions_stand_in_the_reference_and_nothing_of_the_program_does():
    text = Path(spec.ROOT / "reference" / "kda_moe.py").read_text()
    flat = " ".join(text.split())
    for words in ("a VECTOR of dk a head", "`dt_bias` one a channel", "Diag(alpha) S",
                  "ONE `lax.scan` over the tokens", "NO rotary embedding", "no q/k norm",
                  "the gate elementwise", "in the choice, in no weight", "s + 1e-20",
                  "the shared expert unweighted", "(untied)"):
        assert words in flat, words
    said = " ".join(CFG["assumed"])
    for words in ("a VECTOR of dk a head and token", "LOW-RANK pairs", "rank 128 = the head size",
                  "dt_bias one a key channel", "ELEMENTWISE sigmoid gate", "249.9 B",
                  "the bias enters no weight", "their sum + 1e-20", "added unweighted",
                  "no q/k norm and no rotary embedding", "three bfloat16 passes"):
        assert words in said, words
    assert "import pytorch_distributed_example_tpu" not in text
    assert "from pytorch_distributed_example_tpu" not in text
    assert "pallas" not in text.lower() and "gated_delta_chunked" not in text


# --- the glue's counts ---------------------------------------------------------

def test_the_glue_counts_the_cut_and_the_published_model():
    """ISSUE 47's arithmetic: 137.7 M a KDA mixer, 109.1 M a gated GQA mixer,
    15.73 M an expert, 1.31 M a router; 3308 M held, 250.3 B published."""
    d, hw = 4096, 64 * 128
    kda = 4 * d * hw + 2 * (d * 128 + 128 * hw) + d * 64 + 4 * 3 * hw + 64 + hw + 128
    gqa = 3 * d * hw + 2 * d * 1024
    assert glue.linear_sizes(CFG) == (64, 128, 4) and glue.gate_rank(CFG) == 128
    assert glue.routed_experts(CFG) == 320 and glue.linear_layers(CFG) == 3
    assert glue.mixer_params(CFG, 1) == kda == 137_732_288
    assert glue.mixer_params(CFG, 0) == gqa == 109_051_904
    assert glue.expert_params(CFG) == 3 * d * 1280 == 15_728_640
    held = 40 * 15_728_640 + 15_728_640 + d * 320 + 320 + 2 * d
    assert glue.layer_params(CFG, 0) == gqa + held
    assert glue.layer_params(CFG, 1) == pytest.approx(783.9e6, rel=1e-4)
    assert glue.layer_params(CFG["published"], 1) == pytest.approx(5187.9e6, rel=1e-5)
    assert glue.layer_params(CFG["published"], 0) == pytest.approx(5159.3e6, rel=1e-5)
    assert glue.param_count(CFG) == 3_308_353_344
    assert glue.param_count(CFG) == pytest.approx(3308e6, rel=2e-4)
    assert glue.param_count(CFG["published"]) == pytest.approx(250.3e9, rel=1e-4)
    assert 2 * glue.param_count(CFG) == pytest.approx(6.62e9, rel=1e-3)  # bfloat16
    # a gate a head in the 12 softmax layers would count 249.9 B
    per_head = glue.param_count(CFG["published"]) - 12 * (d * hw - d * 64)
    assert per_head == pytest.approx(249.9e9, rel=1e-4)


def test_the_program_holds_what_the_glue_counts():
    """The model's own parameter tree at the published widths, by shape."""
    import jax

    model = modelglue.build_model(CFG, 32768, remat=False)
    shapes = jax.eval_shape(modelglue.init_fn(model, CFG), jax.random.PRNGKey(0))["params"]
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert held == glue.param_count(CFG)
    assert {a.dtype.name for a in jax.tree_util.tree_leaves(shapes)} == {"bfloat16"}
    assert shapes["tok_embed"]["embedding"].shape == (24576, 4096)
    assert shapes["lm_head"]["kernel"].shape == (4096, 24576)
    lin = shapes["layers_1"]["linear_attn"]
    assert lin["f_proj_a"]["kernel"].shape == (4096, 128)
    assert lin["f_proj_b"]["kernel"].shape == lin["g_proj_b"]["kernel"].shape == (128, 8192)
    assert lin["dt_bias"].shape == (64, 128) and lin["A_log"].shape == (64,)
    assert shapes["layers_0"]["attn"]["out_gate"]["kernel"].shape == (4096, 8192)
    assert shapes["layers_0"]["attn"]["k_proj"]["kernel"].shape == (4096, 1024)
    assert shapes["layers_3"]["mlp"]["router"].shape == (4096, 320)
    assert shapes["layers_3"]["mlp"]["experts_gate"].shape == (40, 4096, 1280)
    assert shapes["layers_3"]["mlp"]["shared_expert"]["down_proj"]["kernel"].shape == (1280, 4096)
    cfg = model.cfg
    assert cfg.cache_kinds == ("full", "linear") and cfg.linear_layers == (1, 2, 3)
    assert cfg.experts_held == (0, 40) and cfg.sparse_experts == 320 and cfg.sparse_top_k == 8
    assert (cfg.linear_decay, cfg.linear_gate_rank) == ("channel", 128)
    assert cfg.head_dim == 128 and cfg.kv_heads == 8 and cfg.attn_out_gate


def test_the_flops_count_what_this_chip_computes():
    """Of a token's 8 assignments the held experts' expected share, 8 x 40 /
    320 = 1 expert a token, the shared expert, the mixers, the sliced head."""
    assert glue.held_per_token(CFG) == 1.0 and glue.held_per_token(CFG["published"]) == 8.0
    d, hw, seq = 4096, 8192, 1024
    ffn = 15_728_640 + 15_728_640 + d * 320
    kda = 4 * d * hw + 2 * (d * 128 + 128 * hw) + d * 64
    assert glue.layer_params(CFG, 1, active=True) == kda + ffn
    assert glue.layer_params(CFG, 0, active=True) == 3 * d * hw + 2 * d * 1024 + ffn
    matmuls = sum(glue.layer_params(CFG, i, active=True) for i in range(4)) + d * 24576
    mixing = 4 * hw * (seq + 1) / 2 + 3 * 6 * 64 * 128 * 128
    assert glue.train_flops_per_token(CFG, seq) == pytest.approx(3 * (2 * matmuls + mixing))
    # a decoding token at 8 k keys: ~1.5 GFLOP forward here
    forward = modelglue.forward_flops(CFG)
    assert forward(8191, 1) == pytest.approx(2 * matmuls + 4 * hw * 8192 + 3 * 6 * 64 * 128 * 128,
                                             rel=1e-3)


# --- the roofline counts, by hand ----------------------------------------------

def test_chunk_scan_call_by_hand():
    """A chunk of 416 real tokens in a bucket of 512: the rule's 6 dk dv a
    token and head in 3 layers of 64 heads of 128 x 128, three bfloat16
    passes a float32 product; the state block of 4.19 MB in and out a
    layer."""
    call = glue.chunk_scan_call(CFG, 416, 4)
    assert call["flops"] == 3 * 416 * 3 * 6 * 64 * 128 * 128
    assert call["bytes"] == 3 * 2 * 64 * 128 * 128 * 4 == 25_165_824
    assert glue.chunk_scan_call(CFG, 512, 4)["flops"] == pytest.approx(29.0e9, rel=1e-2)
    # a state kept in bfloat16 would be one pass and half the bytes
    low = glue.chunk_scan_call(CFG, 416, 2)
    assert (low["flops"], low["bytes"]) == (call["flops"] / 3, call["bytes"] / 2)
    # compute-bound: 0.147 ms of products against 0.031 ms of state
    assert call["flops"] / 197e12 > call["bytes"] / 819e9


def test_recurrence_decode_call_by_hand():
    """15 live rows: a row and layer reads and writes 4.19 MB of state, its
    conv tail of 3 x 24576 values, and its vectors (q, k, v, a decay a key
    channel, beta in; the output out)."""
    call = glue.recurrence_decode_call(CFG, 15, 4, 2)
    state = 2 * 64 * 128 * 128 * 4
    tail = 2 * 3 * 24576 * 2
    vectors = (3 * 8192 + 8192 + 64 + 8192) * 2
    assert call["bytes"] == 15 * 3 * (state + tail + vectors)
    assert call["bytes"] == pytest.approx(0.394e9, rel=2e-3)
    assert call["flops"] == 15 * 3 * (4 * 64 * 128 * 128 + 8 * 8192)
    assert glue.recurrence_decode_call(CFG, 0) == {"bytes": 0.0, "flops": 0.0}


def test_moe_decode_call_by_hand():
    """A step of 15 rows: 15 assignments fell to the held experts in each of
    4 layers, 12 distinct experts hit a layer; each layer's router is read
    whatever was hit. The shared expert's FLOPs are counted, its BYTES are
    not: XLA streams them in before the scope the reader divides by starts,
    and a share may read low, never high."""
    call = glue.moe_decode_call(CFG, 15, 4 * 15, [12] * 4, 2)
    router = 4096 * 320
    assert call["bytes"] == (48 * 15_728_640 + 4 * router) * 2
    assert call["flops"] == 2.0 * (60 * 15_728_640 + 15 * 4 * (15_728_640 + router))
    nothing_hit = glue.moe_decode_call(CFG, 15, 0, [0] * 4, 2)
    assert nothing_hit["bytes"] == 4 * router * 2  # no shared expert's bytes
    less = glue.moe_decode_call(CFG, 15, 4 * 15, [12, 12, 12, 6], 2)
    assert call["bytes"] - less["bytes"] == 6 * 15_728_640 * 2


# --- the readers ----------------------------------------------------------------

DEV = "/device:TPU:0"
SCAN = "jit(prefill_chunk)/TransformerLM/layers_{}/linear_attn/chunk_scan/{}"


def _chunk_runs(n_runs, gap=40_000_000, pid=9):
    """`n_runs` runs of the chunk program, each with four operations of the
    scan in each of three linear layers (one of them the running sum of g)
    and one of the low-rank gates outside the scan."""
    ops, runs = [], []
    for i in range(n_runs):
        start = i * gap
        runs.append(("jit_prefill_chunk", pid, start, gap - 1_000_000))
        for layer in (1, 2, 3):
            at = start + 1000 + layer * 8_000_000
            for k, (what, ps) in enumerate((("cumsum", 200_000), ("dot_general", 900_000),
                                            ("while/body/dot_general", 700_000),
                                            ("exp", 200_000))):
                ops.append((SCAN.format(layer, what), pid, at + k * 1_000_000, ps))
            ops.append((SCAN.format(layer, "x").replace("chunk_scan/x", "kda_gate/dot_general"),
                        pid, at + 5_000_000, 300_000))
    return sorted(ops, key=lambda o: o[2]), runs


def _env(config=CFG):
    said = []
    env = ReadEnv(cell={"config": config, "name": "no_such_trace_directory"},
                  samples={}, trace=xplane.Trace(devices={DEV: []}),
                  peaks=PEAKS, chips=1, memory_peak_bytes=0, say=said.append)
    return env, said


def _with(monkeypatch, sc, notes):
    from bench_matrix.readers import scope_time

    monkeypatch.setattr(scope_time, "_scopes", lambda env: sc)
    monkeypatch.setattr(latent_steps, "annotations", lambda env, name: notes)


def test_the_new_roofline_pairs_whole_runs_with_annotated_chunks(monkeypatch):
    ops, runs = _chunk_runs(3)
    _with(monkeypatch, scopes.Scopes(ops={DEV: ops}, runs={DEV: runs}),
          [{"slot": 0, "start": 0, "tokens": 512, "bucket": 512},
           {"slot": 0, "start": 512, "tokens": 512, "bucket": 512},
           {"slot": 1, "start": 1024, "tokens": 416, "bucket": 512}])
    env, said = _env()
    args = spec.load("layer_metrics", "chunk_scan_roofline")["args"]
    got = chunk_scan_roofline.read(args, env)
    need = 3 * (512 + 512 + 416) * 3 * 6 * 64 * 128 * 128
    spent = 3 * 3 * 2_000_000 / 1e12  # three runs x three layers x 2 us under the scope
    assert got == pytest.approx(100 * (need / 197e12) / spent)
    assert "3 dispatches kept, 3 runs" in said[-1] and "3 paired" in said[-1]
    assert "compute-bound" in said[-1]
    # a run cut by the edge of the trace holds fewer operations and is left out
    cut = [o for o in ops if not (o[2] >= 80_000_000 + 20_000_000)]
    _with(monkeypatch, scopes.Scopes(ops={DEV: cut}, runs={DEV: runs}),
          [{"tokens": 512}, {"tokens": 512}, {"tokens": 416}])
    env, said = _env()
    got = chunk_scan_roofline.read(args, env)
    assert "2 of them whole" in said[-1] and "2 paired" in said[-1]
    assert got == pytest.approx(100 * (3 * 1024 * 3 * 6 * 64 * 128 * 128 / 197e12)
                                / (2 * 3 * 2e-6))


def test_nothing_to_read_leaves_the_keys_out(monkeypatch):
    """No annotated chunk, a program without the scope (the parent's: it has
    no such model), a glue without the count, no trace: None, never 0."""
    ops, runs = _chunk_runs(2)
    sc = scopes.Scopes(ops={DEV: ops}, runs={DEV: runs})
    args = spec.load("layer_metrics", "chunk_scan_roofline")["args"]
    _with(monkeypatch, sc, [])
    assert chunk_scan_roofline.read(args, _env()[0]) is None
    _with(monkeypatch, sc, None)
    assert chunk_scan_roofline.read(args, _env()[0]) is None
    _with(monkeypatch, sc, [{"tokens": 512}, {"tokens": 512}])
    assert chunk_scan_roofline.read(args, _env(spec.load("configs", "mistral-7b-v0.3-d16"))[0]) is None
    bare = scopes.Scopes(ops={DEV: [(o[0].replace("chunk_scan", "scan"), *o[1:]) for o in ops]},
                         runs={DEV: runs})
    _with(monkeypatch, bare, [{"tokens": 512}, {"tokens": 512}])
    assert chunk_scan_roofline.read(args, _env()[0]) is None
    env, _ = _env()
    env.trace = None
    _with(monkeypatch, None, [{"tokens": 512}])
    for reader, name in ((chunk_scan_roofline, "chunk_scan_roofline"),
                         (moe_decode_roofline, "moe_decode_roofline"),
                         (recurrence_decode_roofline, "recurrence_decode_roofline")):
        assert reader.read(spec.load("layer_metrics", name)["args"], env) is None


def test_the_scope_metrics_read_the_mixer_s_scopes():
    """`prefill_kda_gate_ms` reads the low-rank pairs and NOT the running sum
    inside the scan: that is the scan's (`kda_prefill_chunk_scan_ms`), each
    metric owns its operations; `kda_prefill_linear_attention_ms` reads all
    of the mixer."""
    ops, runs = _chunk_runs(2)
    sc = scopes.Scopes(ops={DEV: ops}, runs={DEV: runs})
    read = lambda name: scopes.time_in(
        sc, *(spec.load("layer_metrics", name)["args"][k] for k in ("program", "scope")))
    assert read("prefill_kda_gate_ms") == pytest.approx(3 * 0.3e-3)
    assert read("kda_prefill_chunk_scan_ms") == pytest.approx(3 * 2e-3)
    assert read("kda_prefill_linear_attention_ms") == pytest.approx(3 * 2.3e-3)


def test_the_recurrence_roofline_counts_a_vector_decay(monkeypatch):
    """The existing reader serves the cell through this glue's count."""
    from bench_matrix.readers import scope_time

    path = "jit(step)/TransformerLM/layers_{}/linear_attn/recurrence/jit(_call)/pallas_call"
    ops = [(path.format(layer), 7, 1000 + layer * 1_000_000, 200_000_000) for layer in (1, 2, 3)]
    sc = scopes.Scopes(ops={DEV: ops}, runs={DEV: [("jit_step", 7, 0, 10_000_000_000)]})
    monkeypatch.setattr(scope_time, "_scopes", lambda env: sc)
    env, said = _env()
    env.samples["decode_steps"] = [{"keys": [9000] * 15, "distinct": 15 * 9000}]
    got = recurrence_decode_roofline.read(
        spec.load("layer_metrics", "recurrence_decode_roofline")["args"], env)
    need = glue.recurrence_decode_call(CFG, 15, 4, 2)["bytes"]
    assert got == pytest.approx(100 * (need / 819e9) / (3 * 200e-6))
    assert "memory-bound" in said[-1]


def test_serve_mfu_counts_one_held_expert_a_token():
    env, _ = _env()
    env.samples.update(computed={"chunks": [[0, 512], [512, 512]], "decode_keys": [9000] * 15},
                       window=[10.0, 11.0])
    forward = modelglue.forward_flops(CFG)
    want = 100 * (forward(0, 512) + forward(512, 512) + 15 * forward(8999, 1)) / 197e12
    assert serve_mfu.read({}, env) == pytest.approx(want)
    assert 0 < want < 2.0


# --- the traffic mix and the cell ----------------------------------------------

def test_the_traffic_mix_is_the_cell_the_issue_names():
    t = spec.load("traffic", MIX)
    assert t["arrival"] == {"mode": "closed", "clients": 16, "ramp_seconds": 4.0}
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 12288, "sigma": 0.6,
                                  "min": 2048, "max": 30000}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.6,
                                  "min": 128, "max": 2048}
    assert t["engine"] == {
        "block_size": 16, "pool_blocks": 32768, "prefill_chunk_tokens": 512,
        "max_seq_len": 32768, "min_bucket": 128, "kv_quant": False, "prefix_cache": False,
        "temperature": 0.0, "slots": 16}
    assert (t["strata"], t["shared_prefix_tokens"], t["warmup_seconds"],
            t["trace_seconds"], t["throughput_counts"]) == (32, 0, 12, 3, "generated")
    prompts = traffic_gen.length_cycle(t["prompt_tokens"], t["strata"])
    outputs = traffic_gen.length_cycle(t["output_tokens"], t["strata"])
    assert 2048 <= prompts.min() and prompts.max() <= 30000
    assert 128 <= outputs.min() and outputs.max() <= 2048
    assert 12000 < prompts.mean() < 16000 and 500 < outputs.mean() < 700
    eng = t["engine"]
    assert prompts.max() + outputs.max() <= eng["max_seq_len"]
    # nothing is preempted: every slot at the longest sequence the tables hold
    assert eng["slots"] * eng["max_seq_len"] // eng["block_size"] == eng["pool_blocks"]
    # as held: 32768 blocks x 16 tokens x 4096 B (1 layer x 2 x 8 heads x 128 x 2 B)
    assert eng["pool_blocks"] * 16 * 4096 == pytest.approx(2.15e9, rel=2e-3)


def test_the_cell_reports_throughput_and_lists_what_the_issue_lists():
    cell = spec.load_cell(CELL)
    assert cell["config_name"] == NAME and cell["traffic_name"] == MIX
    assert cell["chips"] == 1 and cell["runner"] == "serve"
    assert list(cell["end_to_end"]) == ["serve_tokens_per_s", "setup_s"]
    assert {m["moves"] for m in cell["per_layer"].values()} == {"serve_tokens_per_s", "setup_s"}
    listed = {"compiles_in_window", "decode_slots_mean", "serve_ttft_ms_p50",
              "serve_ttft_ms_p90", "serve_device_idle_pct", "serve_peak_hbm_gb",
              "decode_step_device_ms", "decode_cache_attention_ms", "decode_moe_ms",
              "prefill_moe_ms", "moe_experts_hit_mean", "moe_decode_roofline", "serve_mfu_pct",
              "serve_host_work_ms", "serve_host_wait_pct"}
    assert set(cell["per_layer"]) == set(NEW_METRICS) | set(KDA_METRICS) | listed
    # NOT listed although ISSUE 47 lists them. Two move serve_itl_ms_p90, which
    # the cell does not report: a per-layer metric is listed only where what it
    # moves is. The linear kind's five are held to the Olmo cell ALONE by the
    # benchmark's own test (test_bm_hybrid_linear.py::test_the_cell_reports_
    # throughput_and_lists_only_what_moves_what_it_reports: no other cell may
    # list one), a file this PR may not edit: the mixer keeps the scopes, and
    # the cell reads them under names of its own (`KDA_METRICS`: the accepted
    # file's reader and arguments, letter for letter)
    for name in ("prefill_chunk_device_ms", "prefill_cache_attention_ms"):
        assert spec.load("layer_metrics", name)["moves"] == "serve_itl_ms_p90"
        assert name not in cell["per_layer"]
    for name in ("decode_linear_attention_ms", "prefill_linear_attention_ms",
                 "decode_recurrence_ms", "prefill_chunk_scan_ms", "recurrence_decode_roofline"):
        assert name not in cell["per_layer"]
        assert spec.load("layer_metrics", name)["moves"] == "serve_tokens_per_s"
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, accepted in KDA_METRICS.items():
        assert spec.load("layer_metrics", name) == spec.load("layer_metrics", accepted), name
    for name in NEW_METRICS + tuple(KDA_METRICS):
        m = cell["per_layer"][name]
        roof = name.endswith("_roofline")
        assert m["reader"] == (KDA_METRICS.get(name, name) if roof else "scope_ms")
        assert (m["unit"], m["better"]) == (("%", "higher") if roof else ("ms", "lower"))
        assert m["layer"] == ("kernels" if roof else "model")
        assert by_name[name]["workloads"] == [CELL]
        assert BENCH["per_layer"].index(by_name[name]) >= len(BENCH["per_layer"]) - 7
    for name in listed:
        assert by_name[name]["workloads"][-1] == CELL, name
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": NAME, "traffic": MIX, "chips": 1, "why": cell["why"]}
    assert BENCH["configs"][-1]["name"] == NAME
    assert BENCH["configs"][-1]["reduced"] == CFG["reduced"]
    c = cell["correctness"]
    eng = cell["traffic"]["engine"]
    assert (c["prompt_tokens"], c["decode_positions"], c["last_positions"]) == (4000, 8, 256)
    assert c["prompt_tokens"] % eng["prefill_chunk_tokens"] == 416  # ends inside a bucket
    assert c["last_positions"] <= 416  # every compared row lies in the last chunk
    assert 0 < c["rms_rel"] < c["max_rel"] <= 1 and 0 < c["chosen_gap"] <= 1


def test_the_check_s_replay_has_the_shapes_of_every_engine_that_serves_the_configuration():
    check = CFG["model"]["check"]
    assert check["routing"] == "system" and 0 < check["tie_margin"] < 0.05
    mine = [spec.load_cell(n) for n in spec.names("workloads")
            if spec.load("workloads", n)["config"] == NAME]
    assert [c["name"] for c in mine] == [CELL]
    for cell in mine:
        eng = cell["traffic"]["engine"]
        assert check["replay"] == dict(
            {k: eng[k] for k in ("block_size", "prefill_chunk_tokens", "max_seq_len",
                                 "min_bucket")},
            decoded_tail=cell["correctness"]["decode_positions"])


# --- the glue's replay and the cell end to end, tiny ----------------------------

TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=256, num_hidden_layers=4,
    n_routed_experts=4, num_experts_per_tok=2,
    linear_attn_config=dict(CFG["linear_attn_config"], num_heads=3, head_dim=8),
)


def _tiny_config(dtype="float32"):
    cfg = dict(CFG, **TINY, dtype=dict(CFG["dtype"], **{
        k: dtype for k in ("weights", "activations", "kv_cache", "conv_tail")}))
    cfg["published"] = dict(CFG["published"], **dict(TINY, n_routed_experts=8,
                                                     num_hidden_layers=8))
    cfg["model"] = dict(CFG["model"], check=dict(
        CFG["model"]["check"], tie_margin=0.05,
        replay={"block_size": 8, "prefill_chunk_tokens": 32, "max_seq_len": 128,
                "min_bucket": 16, "decoded_tail": 4}))
    return cfg


def _tiny_cell(dtype):
    import copy

    cell = copy.deepcopy(spec.load_cell(CELL))
    cell["config"] = _tiny_config(dtype)
    t = cell["traffic"]
    t["engine"].update(block_size=8, pool_blocks=64, prefill_chunk_tokens=32,
                       max_seq_len=128, min_bucket=16, slots=4)
    t["arrival"].update(clients=4, ramp_seconds=0.2)
    t["prompt_tokens"].update(median=40, min=16, max=100)
    t["output_tokens"].update(median=6, min=3, max=12)
    t.update(strata=8, warmup_seconds=0.5, trace_seconds=0.5)
    cell["correctness"].update(prompt_tokens=75, decode_positions=4, last_positions=8,
                               max_rel=0.5, rms_rel=0.15, chosen_gap=0.5)
    return cell


def test_the_glue_against_the_reference_at_a_tiny_size():
    import jax.numpy as jnp

    from bench_matrix import correctness

    cfg = _tiny_config()
    model = modelglue.build_model(cfg, 128, remat=False)
    variables = modelglue.make_variables(model, cfg, 5)
    tokens = traffic_gen.check_sequence(256, 9, 70)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    want = correctness.reference_logits(cfg, variables, tokens, 70)
    assert correctness.compare(got, want, {"max_rel": 1e-4, "rms_rel": 1e-4})["ok"]
    emb, layers, norm, w_out = glue.reference_parts(variables)
    assert w_out.shape == (64, 256) and emb.shape == (256, 64) and len(list(layers)) == 4
    names = [sorted(w) for w in layers]
    assert "w_out_gate" in names[0] and "w_f1" not in names[0] and "shared_up" in names[0]
    assert "w_f2" in names[1] and "dt_bias" in names[1] and "w_out_gate" not in names[1]


def test_the_replay_tells_the_experts_the_model_chose_in_the_padded_last_chunk_too():
    """A sequence of 64 + 4 tokens: the prompt's 64 go in two whole chunks;
    one of 75 + 4 ends inside a bucket (32, 32, 11 in a bucket of 16): the
    last chunk is padded as the engine pads it, its rows are told too and the
    state behind its last REAL token is what a cache-free forward sees; the
    decoded tail keeps -1. 4 of 8 experts held: the router's choices range
    over all 8."""
    import jax
    import jax.numpy as jnp

    cfg = _tiny_config()
    model = modelglue.build_model(cfg, 128, remat=False)
    variables = modelglue.make_variables(model, cfg, 5)
    layers = glue.reference_parts(variables)[1]
    for n_prompt in (64, 75):
        tokens = traffic_gen.check_sequence(256, 9, n_prompt + 4)
        _, inter = jax.jit(lambda v, t: model.apply(v, t, mutable=["intermediates"]))(
            variables, jnp.asarray(tokens)[None])
        told = layers.system_routing(tokens, cfg)
        assert sorted(told) == [0, 1, 2, 3]
        for i, got in told.items():
            want = np.asarray(inter["intermediates"][f"layers_{i}"]["mlp"]["moe_chosen"][0][0])
            assert got.shape == (n_prompt + 4, 2) and got.dtype == np.int32
            np.testing.assert_array_equal(np.sort(got[:n_prompt], 1), np.sort(want[:n_prompt], 1))
            assert (got[n_prompt:] == -1).all() and got[:n_prompt].max() >= 4


def test_a_program_from_before_the_vector_decay_refuses_the_configuration_at_once(monkeypatch):
    """The parent's `TransformerConfig` has none of the three fields: the
    glue raises `SpecError` before anything is built."""
    import dataclasses

    from pytorch_distributed_example_tpu.models import transformer

    fields = {k: v for k, v in transformer.TransformerConfig.__dataclass_fields__.items()
              if k not in ("linear_decay", "linear_gate_rank", "attn_out_gate")}
    old = dataclasses.make_dataclass("TransformerConfig", [(k, v.type, v) for k, v in fields.items()])
    monkeypatch.setattr(transformer, "TransformerConfig", old)
    with pytest.raises(spec.SpecError, match="linear_decay"):
        modelglue.build_model(CFG, 128, remat=False)


def test_runner_gives_the_contract_line_for_the_cell_at_a_tiny_preset(capsys):
    """`run.execute` over the real cell's files with sizes cut in the test:
    bfloat16 as the cell runs, the prompt ending inside a bucket, the
    reference told the system's routing; `correct`, and only the cell's two
    end-to-end metrics."""
    import jax

    from _tiny import FAKE_PEAKS, context
    from bench_matrix import run

    cell = _tiny_cell("bfloat16")
    ctx = context(1.0, jax.devices()[:1])
    try:
        line = run.execute(cell, ctx, FAKE_PEAKS, {"platform": "cpu", "kind": "cpu", "count": 1})
    finally:
        ctx.compiles.close()
    said = capsys.readouterr()
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "correctness: prefill of 75 tokens" in said.out
    assert "choices told by the system" in said.err


def test_a_traced_run_of_the_tiny_cell_reports_its_counters_and_leaves_device_metrics_out(
        tmp_path, monkeypatch):
    """`--trace 1` on the CPU: the run's own counters are reported, every
    metric that needs a device plane leaves its key out (never 0), and the
    engine wrote one `serve:prefill_chunk` annotation a chunk for the new
    roofline to pair with."""
    import jax

    from _tiny import FAKE_PEAKS, context
    from bench_matrix import run

    cell = _tiny_cell("float32")
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    trace_dir = str(tmp_path / "trace" / CELL)
    ctx = context(1.0, jax.devices()[:1], trace_dir=trace_dir)
    ctx.samples_path = str(tmp_path / "samples.json")
    try:
        try:
            line = run.execute(cell, ctx, FAKE_PEAKS,
                               {"platform": "cpu", "kind": "cpu", "count": 1})
        except RuntimeError as e:  # a CPU trace holds no device operation
            assert "no device operation" in str(e)
            line = None
    finally:
        ctx.compiles.close()
    samples = json.loads(Path(ctx.samples_path).read_text())
    steps = samples["decode_steps"]
    assert steps and all(len(s["keys"]) >= 1 for s in steps)
    env = ReadEnv(cell=cell, samples=samples, trace=xplane.Trace(devices={}), peaks=FAKE_PEAKS,
                  chips=1, memory_peak_bytes=0, say=lambda text: None)
    notes = latent_steps.annotations(env, "serve:prefill_chunk")
    assert notes and all(0 < n["tokens"] <= n["bucket"] <= 32 for n in notes)
    if line is not None:
        for name in ("chunk_scan_roofline", "recurrence_decode_roofline", "moe_decode_roofline",
                     "prefill_kda_gate_ms"):
            assert name not in line["metrics"], name
        assert line["metrics"]["compiles_in_window"]["value"] == 0
