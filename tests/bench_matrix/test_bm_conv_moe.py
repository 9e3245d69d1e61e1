"""The `lfm2-8b-a1b-e16` configuration and what came with it: the rule for a
cut on its file, the glue's counts against the published sizes, the new
roofline counts by hand, its reader's pairing of whole runs with kept
steps, the traffic mix, the check's replay, and the cell end to end at a tiny
preset."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench_matrix import modelglue, spec, traffic_gen
from bench_matrix.glue import conv_moe as glue
from bench_matrix.readers import (
    ReadEnv, gqa64_decode_roofline, latent_steps, moe_decode_roofline, serve_mfu,
)
from bench_matrix.reduce import scopes, xplane

from test_bm_specs import check_cut, depth_floor

NAME, CELL = "lfm2-8b-a1b-e16", "serve_lfm2_assist_c64"
CFG = spec.load("configs", NAME)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


# --- the file ----------------------------------------------------------------

def test_the_file_holds_every_published_key_and_cuts_the_experts_alone():
    check_cut(CFG)
    pub = CFG["published"]
    assert CFG["reduced"] == ["num_experts"]
    assert (CFG["num_experts"], pub["num_experts"]) == (16, 32)
    for key, value in pub.items():
        if key != "num_experts":
            assert CFG[key] == value, key
    assert (CFG["num_hidden_layers"], CFG["hidden_size"], CFG["vocab_size"],
            CFG["num_experts_per_tok"], CFG["num_dense_layers"], CFG["conv_L_cache"]) == (
        24, 2048, 65536, 4, 2, 3)
    assert CFG["layer_types"].count("conv") == 18
    assert CFG["layer_types"].count("full_attention") == 6
    # the period of four breaks in the last four layers: the floor is 20, and
    # the depth is not cut
    assert depth_floor(pub) == 20 and CFG["num_hidden_layers"] == pub["num_hidden_layers"]
    assert [a[:3] for a in CFG["assumed"][:8]] == [f"({i})" for i in range(1, 9)]
    assert "2 chips" in CFG["deployment"] and "experts 0-15" in CFG["deployment"]
    assert "32 rows a chip" in CFG["deployment"]
    for words in ("depth_floor reads 20", "4464.4 M", "8.93 GB", "8339.9 M", "3.22 GB",
                  "What the cut distorts", "64 rows"):
        assert words in CFG["reduction_notes"], words
    assert CFG["dtype"] == {
        "weights": "bfloat16", "activations": "bfloat16", "logits": "float32",
        "router": "float32", "conv_mixer": "float32", "conv_tail": "bfloat16",
        "kv_cache": "bfloat16"}
    assert len(CFG["why"]) <= 200 and len(CFG["source"]) <= 200


def test_the_file_s_published_keys_are_the_catalog_row_s():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    (row,) = [r for r in rows if r["name"] == "LFM2-8B-A1B"]
    assert CFG["published"] == row["config"] and CFG["source"] == row["source_url"]
    assert row["head_dim"] is None and "tie_word_embeddings" not in row["config"]


def test_the_assumptions_stand_in_the_reference_and_nothing_of_the_program_does():
    text = Path(spec.ROOT / "reference" / "conv_moe.py").read_text()
    flat = " ".join(text.split())
    for words in ("[B, C, u] = split3", "no activation", "EACH head's", "value j + head/2",
                  "sum of the chosen s + 1e-6", "no shared expert", "a tied head",
                  "in the choice, in no weight"):
        assert words in flat, words
    said = " ".join(CFG["assumed"])
    for words in ("B, C, u IN THAT ORDER", "no activation anywhere inside", "EACH head's 64 values",
                  "value j + 32", "their sum + 1e-6", "no shared expert", "TIED to the embedding",
                  "the bias enters no weight", "head size 64 = hidden_size / num_attention_heads"):
        assert words in said, words
    assert "import pytorch_distributed_example_tpu" not in text
    assert "from pytorch_distributed_example_tpu" not in text
    assert "pallas" not in text.lower().replace("no kernels", "")


# --- the glue's counts ---------------------------------------------------------

def test_the_glue_counts_the_cut_and_the_published_model():
    """ISSUE 43's arithmetic: 16.78 M a conv operator, 10.49 M an attention
    operator, 44.04 M a dense ffn, 11.01 M an expert, 176.23 M a sparse ffn
    with 16 experts held; 4464.4 M held, 8339.9 M published (tied)."""
    d = 2048
    conv = 3 * d * d + d * d + 3 * d
    attn = 2 * d * d + 2 * d * 512 + 2 * 64
    assert glue.head_dim(CFG) == 64 and glue.routed_experts(CFG) == 32
    assert (glue.conv_layers(CFG), glue.attention_layers(CFG)) == (18, 6)
    assert glue.mixer_params(CFG, 0) == conv == 16_783_360
    assert glue.mixer_params(CFG, 2) == attn == 10_485_888
    assert glue.expert_params(CFG) == 3 * d * 1792 == 11_010_048
    assert glue.layer_params(CFG, 0) == conv + 3 * d * 7168 + 2 * d
    sparse = 16 * 11_010_048 + d * 32 + 32
    assert sparse == pytest.approx(176.23e6, rel=1e-4)
    assert glue.layer_params(CFG, 2) == attn + sparse + 2 * d
    assert glue.layer_params(CFG["published"], 3) == conv + 32 * 11_010_048 + d * 32 + 32 + 2 * d
    assert glue.param_count(CFG) == 4_464_393_664
    assert glue.param_count(CFG) == pytest.approx(4464.4e6, rel=1e-5)
    assert glue.param_count(CFG["published"]) == 8_339_930_560
    assert glue.param_count(CFG["published"]) == pytest.approx(8339.9e6, rel=1e-5)
    assert glue.param_count(CFG["published"]) + 65536 * d == pytest.approx(8474e6, rel=1e-4)
    assert 2 * glue.param_count(CFG) == pytest.approx(8.93e9, rel=1e-3)  # bfloat16


def test_the_program_holds_what_the_glue_counts():
    """The model's own parameter tree at the published widths, by shape."""
    import jax

    model = modelglue.build_model(CFG, 4096, remat=False)
    shapes = jax.eval_shape(modelglue.init_fn(model, CFG), jax.random.PRNGKey(0))["params"]
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert held == glue.param_count(CFG)
    assert {a.dtype.name for a in jax.tree_util.tree_leaves(shapes)} == {"bfloat16"}
    assert "lm_head" not in shapes and shapes["tok_embed"]["embedding"].shape == (65536, 2048)
    assert shapes["layers_0"]["gated_conv"]["in_proj"]["kernel"].shape == (2048, 6144)
    assert shapes["layers_2"]["attn"]["q_norm"]["scale"].shape == (64,)
    assert shapes["layers_2"]["mlp"]["router"].shape == (2048, 32)
    assert shapes["layers_2"]["mlp"]["experts_gate"].shape == (16, 2048, 1792)
    assert "router" not in shapes["layers_1"]["mlp"]
    cfg = model.cfg
    assert cfg.head_dim == 64 and cfg.kv_heads == 8 and cfg.experts_held == (0, 16)
    assert len(cfg.conv_layers) == 18 and cfg.cache_kinds == ("full", "conv")


def test_the_flops_count_what_this_chip_computes():
    """Of a token's 4 assignments the held experts' expected share, 4 x 16 /
    32 = 2: the count feeds `serve_mfu_pct`, a share of THIS chip's peak, so
    the lesser count is the safe one. The published model, all 32 held,
    counts all 4."""
    assert glue.held_per_token(CFG) == 2.0 and glue.held_per_token(CFG["published"]) == 4.0
    d, seq = 2048, 1024
    sparse = 2 * 11_010_048 + d * 32
    assert glue.layer_params(CFG, 3, active=True) == 4 * d * d + sparse
    assert glue.layer_params(CFG, 2, active=True) == 2 * d * d + 2 * d * 512 + sparse
    matmuls = sum(glue.layer_params(CFG, i, active=True) for i in range(24)) + d * 65536
    assert matmuls == 18 * 4 * d * d + 6 * 10_485_760 + 2 * 3 * d * 7168 + 22 * sparse + d * 65536
    mixing = 6 * 4 * d * (seq + 1) / 2 + 18 * 8 * d
    assert glue.train_flops_per_token(CFG, seq) == pytest.approx(3 * (2 * matmuls + mixing))
    # a decoding token at 1 k keys: ~2.2 GFLOP forward here (~3.2 with all 4)
    forward = modelglue.forward_flops(CFG)
    assert forward(1023, 1) == pytest.approx(2.22e9, rel=2e-2)


# --- the roofline counts, by hand ----------------------------------------------

def test_moe_decode_call_by_hand():
    """A step of 64 rows: 128 assignments fell to the held experts over 22
    layers each (2816 in all), every one of the 16 hit in every layer."""
    call = glue.moe_decode_call(CFG, 64, 22 * 128, [16] * 22, 2)
    assert call["bytes"] == (22 * 16 * 11_010_048 + 22 * 2048 * 32) * 2
    assert call["bytes"] == pytest.approx(7.75e9, rel=2e-3)
    assert call["flops"] == 2.0 * (22 * 128 * 11_010_048 + 64 * 22 * 2048 * 32)
    # half the experts hit in one layer: their weights are not needed
    less = glue.moe_decode_call(CFG, 64, 22 * 128, [16] * 21 + [8], 2)
    assert call["bytes"] - less["bytes"] == 8 * 11_010_048 * 2


def test_gqa_decode_call_by_hand():
    """Three rows that attend 700, 1200 and 90 keys, no two of them one
    block: 6 layers x 8 heads x 64 values of K and of V a key, whatever the
    pool's rows hold; a shared block's keys once."""
    keys = [700, 1200, 90]
    call = glue.gqa_decode_call(CFG, keys, sum(keys), 2)
    assert call["bytes"] == 6 * 1990 * 8 * 64 * 2 * 2 == 1990 * 12288
    assert call["flops"] == 6 * 4.0 * 1990 * 32 * 64
    shared = glue.gqa_decode_call(CFG, keys, 1990 - 512, 2)
    assert shared["flops"] == call["flops"] and shared["bytes"] == (1990 - 512) * 12288
    # ~65 k keys a step in the cell: 0.8 GB beside 8.9 GB of weights
    assert glue.gqa_decode_call(CFG, [1024] * 64, 65536, 2)["bytes"] == pytest.approx(
        0.805e9, rel=1e-3)


# --- the readers ----------------------------------------------------------------

DEV = "/device:TPU:0"
CONV = "jit(step)/TransformerLM/layers_{}/gated_conv/{}"
ATTN = ("jit(step)/TransformerLM/layers_{}/attn/cache_attention/jit(_per_device)/"
        "paged_decode_attention/pallas_call")


def _step_runs(n_runs, gap=40_000_000, pid=7):
    """`n_runs` runs of the step, each with three operations a conv layer
    under `gated_conv` (two layers) and one kernel call an attention layer
    (two layers)."""
    ops, runs = [], []
    for i in range(n_runs):
        start = i * gap
        runs.append(("jit_step", pid, start, gap - 1_000_000))
        for layer in (0, 1):
            for k, (what, ps) in enumerate((("in_proj/dot_general", 3_000_000),
                                            ("conv_step/mul", 500_000),
                                            ("out_proj/dot_general", 1_500_000))):
                ops.append((CONV.format(layer, what), pid,
                            start + 1000 + layer * 6_000_000 + k * 1_900_000, ps))
        for layer in (2, 6):
            ops.append((ATTN.format(layer), pid, start + 14_000_000 + layer * 1_000_000,
                        2_000_000))
    return sorted(ops, key=lambda o: o[2]), runs


def _env(steps, config=CFG):
    said = []
    env = ReadEnv(cell={"config": config, "name": "no_such_trace_directory"},
                  samples={"decode_steps": steps}, trace=xplane.Trace(devices={DEV: []}),
                  peaks=PEAKS, chips=1, memory_peak_bytes=0, say=said.append)
    return env, said


def test_the_new_roofline_pairs_whole_runs_with_kept_steps(monkeypatch):
    from bench_matrix.readers import scope_time

    ops, runs = _step_runs(3)
    sc = scopes.Scopes(ops={DEV: ops}, runs={DEV: runs})
    monkeypatch.setattr(scope_time, "_scopes", lambda env: sc)
    steps = [{"keys": [700, 1200, 90], "distinct": 1990},
             {"keys": [701, 1201, 91], "distinct": 1993},
             {"keys": [702, 1202], "distinct": 1904}]
    env, said = _env(steps)
    args = spec.load("layer_metrics", "gqa64_decode_roofline")["args"]
    got = gqa64_decode_roofline.read(args, env)
    need = sum(s["distinct"] for s in steps) * 12288
    assert got == pytest.approx(100 * (need / 819e9) / (3 * 2 * 2_000_000 / 1e12))
    assert "3 dispatches kept, 3 runs" in said[-1] and "3 paired" in said[-1]
    assert "memory-bound" in said[-1]
    # a step the runner could not count goes with its run
    steps[1]["distinct"] = None
    env, said = _env(steps)
    got = gqa64_decode_roofline.read(args, env)
    assert got == pytest.approx(100 * ((1990 + 1904) * 12288 / 819e9) / (2 * 2 * 2e-6))
    assert "2 counted" in said[-1]


def test_nothing_to_read_leaves_the_key_out(monkeypatch):
    """No kept step, a program without the scope (the parent's), a glue
    without the count: None, never 0."""
    from bench_matrix.readers import scope_time

    ops, runs = _step_runs(2)
    sc = scopes.Scopes(ops={DEV: ops}, runs={DEV: runs})
    monkeypatch.setattr(scope_time, "_scopes", lambda env: sc)
    for reader, name in ((gqa64_decode_roofline, "gqa64_decode_roofline"),):
        args = spec.load("layer_metrics", name)["args"]
        env, _ = _env([])
        assert reader.read(args, env) is None
        env, _ = _env([{"keys": [5], "distinct": 5}], spec.load("configs", "mistral-7b-v0.3-d16"))
        assert reader.read(args, env) is None
        bare = scopes.Scopes(ops={DEV: [(o[0].replace("gated_conv", "mixer").replace(
            "cache_attention", "attention"), *o[1:]) for o in ops]}, runs={DEV: runs})
        monkeypatch.setattr(scope_time, "_scopes", lambda env: bare)
        env, _ = _env([{"keys": [5], "distinct": 5}])
        assert reader.read(args, env) is None
        monkeypatch.setattr(scope_time, "_scopes", lambda env: sc)
    env, _ = _env([{"keys": [5], "distinct": 5}])
    env.trace = None
    assert moe_decode_roofline.read(
        spec.load("layer_metrics", "moe_decode_roofline")["args"], env) is None


def test_the_scope_metrics_read_the_mixer_s_scope():
    ops, runs = _step_runs(2)
    chunk = "jit(prefill_chunk)/TransformerLM/layers_0/gated_conv/conv_chunk/mul"
    ops = ops + [(chunk, 9, 90_000_000, 4_000_000_000)]
    runs = runs + [("jit_prefill_chunk", 9, 90_000_000, 8_000_000_000)]
    sc = scopes.Scopes(ops={DEV: ops}, runs={DEV: runs})
    read = lambda name: scopes.time_in(
        sc, *(spec.load("layer_metrics", name)["args"][k] for k in ("program", "scope")))
    assert read("decode_gated_conv_ms") == pytest.approx(2 * 5e-3)
    assert read("prefill_gated_conv_ms") == pytest.approx(4.0)
    assert read("decode_cache_attention_ms") == pytest.approx(2 * 2e-3)


def test_serve_mfu_counts_two_experts_a_token():
    """The window's model FLOPs by the glue's count: a decoded token at k
    keys is `forward_flops(k - 1, 1)`."""
    env, _ = _env([])
    env.samples.update(computed={"chunks": [[0, 512]], "decode_keys": [1024] * 64},
                       window=[10.0, 11.0])
    forward = modelglue.forward_flops(CFG)
    want = 100 * (forward(0, 512) + 64 * forward(1023, 1)) / 197e12
    assert serve_mfu.read({}, env) == pytest.approx(want)
    assert 0 < want < 1.0  # 64 decoded tokens and a chunk in a second: 0.6 %


# --- the traffic mix and the cell ----------------------------------------------

def test_the_traffic_mix_is_the_cell_the_issue_names():
    t = spec.load("traffic", "assist_closed_c64")
    assert t["arrival"] == {"mode": "closed", "clients": 64, "ramp_seconds": 4.0}
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.8,
                                  "min": 64, "max": 2048}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.6,
                                  "min": 96, "max": 1536}
    assert t["engine"] == {
        "block_size": 16, "pool_blocks": 16384, "prefill_chunk_tokens": 512,
        "max_seq_len": 4096, "min_bucket": 128, "kv_quant": False, "prefix_cache": False,
        "temperature": 0.0, "slots": 64}
    assert (t["strata"], t["shared_prefix_tokens"], t["warmup_seconds"],
            t["trace_seconds"], t["throughput_counts"]) == (64, 0, 8, 3, "generated")
    prompts = traffic_gen.length_cycle(t["prompt_tokens"], t["strata"])
    outputs = traffic_gen.length_cycle(t["output_tokens"], t["strata"])
    assert 64 <= prompts.min() and prompts.max() <= 2048
    assert 96 <= outputs.min() and outputs.max() <= 1536
    eng = t["engine"]
    assert prompts.max() + outputs.max() <= 3584 <= eng["max_seq_len"]
    # nothing is preempted: every slot at its longest request
    assert eng["slots"] * 3584 // eng["block_size"] == 14336 <= eng["pool_blocks"]
    # as held: 16384 blocks x 16 tokens x 12288 B (6 layers x 2 x 8 heads x 64 x 2 B)
    assert eng["pool_blocks"] * 16 * 12288 == pytest.approx(3.22e9, rel=1e-3)


def test_the_cell_reports_throughput_and_lists_what_the_issue_lists():
    cell = spec.load_cell(CELL)
    assert cell["config_name"] == NAME and cell["chips"] == 1 and cell["runner"] == "serve"
    assert list(cell["end_to_end"]) == ["serve_tokens_per_s", "setup_s"]
    assert {m["moves"] for m in cell["per_layer"].values()} == {"serve_tokens_per_s", "setup_s"}
    new = {"decode_gated_conv_ms", "prefill_gated_conv_ms", "gqa64_decode_roofline"}
    # no share of a roofline for the conv operators: XLA streams their
    # matrices into on-chip memory under the operations before them, so the
    # scope's own time leaves the stream out and a share of it read 145 %
    assert "gated_conv_decode_roofline" not in spec.names("layer_metrics")
    listed = {"compiles_in_window", "decode_slots_mean", "serve_ttft_ms_p50",
              "serve_ttft_ms_p90", "serve_device_idle_pct", "serve_peak_hbm_gb",
              "decode_step_device_ms", "decode_cache_attention_ms", "decode_moe_ms",
              "prefill_moe_ms", "moe_experts_hit_mean", "moe_decode_roofline", "serve_mfu_pct",
              "serve_host_work_ms", "serve_host_wait_pct"}
    assert set(cell["per_layer"]) == new | listed
    for name in new:
        m = cell["per_layer"][name]
        roof = name.endswith("_roofline")
        assert m["reader"] == (name if roof else "scope_ms")
        assert (m["unit"], m["better"]) == (("%", "higher") if roof else ("ms", "lower"))
        assert m["layer"] == ("kernels" if roof else "model")
        assert next(x for x in BENCH["per_layer"] if x["name"] == name)["workloads"] == [CELL]
    for name in listed:
        assert CELL in next(x for x in BENCH["per_layer"] if x["name"] == name)["workloads"]
    # every roofline and every share of a peak that moves what the cell reports
    for m in BENCH["per_layer"]:
        if CELL in m["workloads"] and ("roofline" in m["name"] or "mfu" in m["name"]):
            assert m["moves"] == "serve_tokens_per_s"
    c = cell["correctness"]
    eng = cell["traffic"]["engine"]
    assert (c["prompt_tokens"], c["decode_positions"], c["last_positions"]) == (2000, 8, 256)
    assert c["prompt_tokens"] % eng["prefill_chunk_tokens"] == 464  # ends inside a bucket
    assert c["last_positions"] <= 464  # every compared row lies in the last chunk
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
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    moe_intermediate_size=32, vocab_size=256, num_hidden_layers=4,
    layer_types=["conv", "conv", "full_attention", "conv"], num_dense_layers=1,
    num_experts=4, num_experts_per_tok=2,
)


def _tiny_config(dtype="float32"):
    cfg = dict(CFG, **TINY, dtype=dict(CFG["dtype"], **{
        k: dtype for k in ("weights", "activations", "kv_cache", "conv_tail")}))
    cfg["published"] = dict(CFG["published"], **dict(TINY, num_experts=8))
    cfg["model"] = dict(CFG["model"], check=dict(
        CFG["model"]["check"], tie_margin=0.05,
        replay={"block_size": 8, "prefill_chunk_tokens": 32, "max_seq_len": 128,
                "min_bucket": 16, "decoded_tail": 4}))
    return cfg


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
    assert w_out.shape == (64, 256) and len(list(layers)) == 4
    names = [sorted(w) for w in layers]
    assert "w_in" in names[0] and "w_gate" in names[0] and "router" not in names[0]
    assert "wq" in names[2] and "router_bias" in names[2] and "w_in" not in names[2]


def test_the_replay_tells_the_experts_the_model_chose_in_the_padded_last_chunk_too():
    """A sequence of 64 + 4 tokens: the prompt's 64 go in two whole chunks;
    one of 75 + 4 ends inside a bucket (32, 32, 11 in a bucket of 16): the
    last chunk is padded as the engine pads it, its rows are told too and the
    tail behind its last REAL token is what a cache-free forward sees; the
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
        assert sorted(told) == [1, 2, 3]
        for i, got in told.items():
            want = np.asarray(inter["intermediates"][f"layers_{i}"]["mlp"]["moe_chosen"][0][0])
            assert got.shape == (n_prompt + 4, 2) and got.dtype == np.int32
            np.testing.assert_array_equal(np.sort(got[:n_prompt], 1), np.sort(want[:n_prompt], 1))
            assert (got[n_prompt:] == -1).all() and got[:n_prompt].max() >= 4


def test_runner_gives_the_contract_line_for_the_cell_at_a_tiny_preset(capsys):
    """`run.execute` over the real cell's files with sizes cut in the test:
    bfloat16 as the cell runs, the prompt ending inside a bucket, the
    reference told the system's routing; `correct`, and only the cell's two
    end-to-end metrics."""
    import copy

    import jax

    from _tiny import FAKE_PEAKS, context
    from bench_matrix import run

    cell = copy.deepcopy(spec.load_cell(CELL))
    cell["config"] = _tiny_config("bfloat16")
    t = cell["traffic"]
    t["engine"].update(block_size=8, pool_blocks=64, prefill_chunk_tokens=32,
                       max_seq_len=128, min_bucket=16, slots=4)
    t["arrival"].update(clients=4, ramp_seconds=0.2)
    t["prompt_tokens"].update(median=40, min=16, max=100)
    t["output_tokens"].update(median=6, min=3, max=12)
    t.update(strata=8, warmup_seconds=0.5, trace_seconds=0.5)
    cell["correctness"].update(prompt_tokens=75, decode_positions=4, last_positions=8,
                               max_rel=0.5, rms_rel=0.15, chosen_gap=0.5)
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
        tmp_path, monkeypatch, capsys):
    """`--trace 1` on the CPU: the run's own counters are reported, every
    metric that needs a device plane leaves its key out (never 0), and the
    runner kept each traced step's rows, keys and distinct keys for the two
    new rooflines."""
    import copy

    import jax

    from _tiny import FAKE_PEAKS, context
    from bench_matrix import run

    cell = copy.deepcopy(spec.load_cell(CELL))
    cell["config"] = _tiny_config("float32")
    t = cell["traffic"]
    t["engine"].update(block_size=8, pool_blocks=64, prefill_chunk_tokens=32,
                       max_seq_len=128, min_bucket=16, slots=4)
    t["arrival"].update(clients=4, ramp_seconds=0.2)
    t["prompt_tokens"].update(median=40, min=16, max=100)
    t["output_tokens"].update(median=6, min=3, max=12)
    t.update(strata=8, warmup_seconds=0.5, trace_seconds=0.5)
    cell["correctness"].update(prompt_tokens=75, decode_positions=4, last_positions=8,
                               max_rel=0.5, rms_rel=0.15, chosen_gap=0.5)
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
    assert all(s["distinct"] == sum(s["keys"]) for s in steps)  # no two rows share a block
    if line is not None:
        assert "gqa64_decode_roofline" not in line["metrics"]
        assert line["metrics"]["compiles_in_window"]["value"] == 0
