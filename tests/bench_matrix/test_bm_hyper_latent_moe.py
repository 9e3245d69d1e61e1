"""The `xing4.0-29b-a4b-d8` configuration and what came with it: the rule for
a cut on its file, the glue's counts against the published sizes, the latent
roofline counts at 32 heads and the streams' byte count by hand, the reader
of `hc_chunk_roofline`, the traffic mix, the check's replay of a prompt whose
head was attached, and the cell end to end at a tiny preset."""

import copy
import json
from pathlib import Path

import numpy as np
import pytest

from bench_matrix import spec, traffic_gen
from bench_matrix.glue import hyper_latent_moe as glue
from bench_matrix.readers import ReadEnv, hc_chunk_roofline, latent_steps
from bench_matrix.reduce import scopes, xplane

from test_bm_specs import check_cut

NAME, CELL, MIX = "xing4.0-29b-a4b-d8", "serve_xing_agent_prefix_c32", "agent_prefix_closed_c32_12k"
CFG = spec.load("configs", NAME)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
NEW_METRICS = ("decode_hc_ms", "prefill_hc_ms", "decode_hc_sinkhorn_ms", "hc_chunk_roofline")


# --- the file ----------------------------------------------------------------

def test_the_file_holds_every_published_key_and_cuts_depth_alone():
    check_cut(CFG)
    pub = CFG["published"]
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert (CFG["num_hidden_layers"], pub["num_hidden_layers"]) == (8, 40)
    for key, value in pub.items():
        if key != "num_hidden_layers":
            assert CFG[key] == value, key
    assert (CFG["first_k_dense_replace"], CFG["n_routed_experts"], CFG["num_experts_per_tok"],
            CFG["vocab_size"], CFG["num_nextn_predict_layers"]) == (2, 64, 4, 131072, 1)
    assert (CFG["hc_mult"], CFG["hc_sinkhorn_iters"], CFG["hc_eps"], CFG["mhc_h_res_clamp_min"],
            CFG["mhc_h_res_clamp_max"]) == (4, 20, 1e-6, -30, 30)
    assert CFG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    assert [a[:3] for a in CFG["assumed"][:8]] == [f"({i})" for i in range(1, 9)]
    assert "one chip holds each layer whole" in CFG["deployment"]
    assert "MTP module" in CFG["deployment"] and "41st block" in CFG["reduction_notes"]
    assert "What the cut distorts" in CFG["reduction_notes"]
    assert CFG["dtype"] == {"weights": "bfloat16", "activations": "bfloat16", "logits": "float32",
                            "router": "float32", "hyper_connection_maps": "float32",
                            "kv_cache": "bfloat16"}
    assert len(CFG["why"]) <= 200 and len(CFG["source"]) <= 200


def test_the_file_s_published_keys_are_the_catalog_row_s():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"]
    assert CFG["published"] == row["config"] and CFG["source"] == row["source_url"]


def test_the_assumptions_stand_in_the_reference_in_the_same_words():
    text = Path(spec.ROOT / "reference" / "hyper_latent_moe.py").read_text()
    flat = " ".join(text.split())
    for said in CFG["assumed"][:8]:
        words = " ".join(said[4:].split())
        assert words.rstrip(";") in flat.replace("hc_* key", "hc_* key"), words[:60]
    assert "import pytorch_distributed_example_tpu" not in text
    assert "from pytorch_distributed_example_tpu" not in text
    assert "for _ in range(1 if fault ==" in text  # the Sinkhorn loop is a Python loop


REFUSED = {
    "five_layers": ({"num_hidden_layers": 5}, "under the floor of 6"),
    "one_dense_layer": ({"first_k_dense_replace": 1}, "exactly the keys that differ"),
    "half_the_experts": (
        {"n_routed_experts": 32, "reduced": CFG["reduced"] + ["n_routed_experts"]},
        "number of chips"),
    "two_streams": ({"hc_mult": 2, "reduced": CFG["reduced"] + ["hc_mult"]}, "must equal"),
    "ten_sinkhorn_iterations": (
        {"hc_sinkhorn_iters": 10, "reduced": CFG["reduced"] + ["hc_sinkhorn_iters"]},
        "must equal"),
    "a_smaller_yarn_factor": (
        {"rope_scaling": dict(CFG["rope_scaling"], factor=8),
         "reduced": CFG["reduced"] + ["rope_scaling"]}, "must equal"),
    "two_experts_a_token": (
        {"num_experts_per_tok": 2, "reduced": CFG["reduced"] + ["num_experts_per_tok"]},
        "must equal"),
    "a_narrower_latent": (
        {"kv_lora_rank": 256, "reduced": CFG["reduced"] + ["kv_lora_rank"]}, "must equal"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_rule_for_a_cut_refuses(case):
    change, word = REFUSED[case]
    with pytest.raises(AssertionError, match=word):
        check_cut(dict(CFG, **change))


# --- the glue's counts --------------------------------------------------------

def test_the_glue_counts_the_cut_and_the_published_model():
    """ISSUE 38's arithmetic: 28.41 M an attention block, 128.20 M a dense
    layer, 40.35 M a sparse layer outside its 64 experts of 11.01 M each
    (744.99 M in all), 0.69 M of maps a block, 939.5 M in embedding and head;
    5665.9 M held, 29.51 B published."""
    d = 3584
    attn = d * 768 + 768 + 768 * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 32 * 128 * d
    assert glue.attention_params(CFG) == attn == 28_411_136
    assert glue.expert_params(CFG) == 3 * d * 1024 == 11_010_048
    maps = 4 * d * 24 + 3 + 24
    assert glue.hc_params(CFG) == maps and 2 * maps == pytest.approx(0.69e6, rel=5e-3)
    assert glue.hc_params(CFG, collapse=True) == 4 * d * 4 + 1 + 4
    dense = attn + 2 * d + 2 * maps + 3 * d * 9216
    assert glue.layer_params(CFG, 0) == glue.layer_params(CFG, 1) == dense
    assert dense == pytest.approx(128.20e6, rel=1e-4)
    outside = attn + 2 * d + 2 * maps + d * 64 + 64 + 11_010_048
    assert outside == pytest.approx(40.35e6, rel=1e-3)
    assert glue.layer_params(CFG, 2) == glue.layer_params(CFG, 7) == outside + 64 * 11_010_048
    assert glue.layer_params(CFG, 2) == pytest.approx(744.99e6, rel=1e-5)
    assert glue.layer_params(CFG, 2, active=True) == outside + 4 * 11_010_048
    assert 2 * 131072 * d == pytest.approx(939.52e6, rel=1e-5)
    assert glue.param_count(CFG) == (2 * dense + 6 * glue.layer_params(CFG, 2)
                                     + glue.hc_params(CFG, collapse=True) + d + 2 * 131072 * d)
    assert glue.param_count(CFG) == pytest.approx(5665.9e6, rel=1e-5)
    assert glue.param_count(CFG["published"]) == pytest.approx(29.51e9, rel=2e-4)
    assert 2 * glue.param_count(CFG) == pytest.approx(11.33e9, rel=1e-3)  # bfloat16
    # what a token meets outside the embedding's lookup: the published "A4B"
    active = glue.param_count(CFG["published"], active=True) - 131072 * d
    assert active == pytest.approx(3.93e9, rel=5e-3)


def test_the_program_holds_what_the_glue_counts():
    """The model's own parameter tree at the published widths, by shape."""
    import jax

    from bench_matrix import modelglue

    model = modelglue.build_model(CFG, 16384, remat=False)
    tree = jax.eval_shape(modelglue.init_fn(model, CFG), jax.random.PRNGKey(0))["params"]
    held = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    assert held == glue.param_count(CFG)
    assert tree["layers_2"]["mlp"]["experts_gate"].shape == (64, 3584, 1024)
    assert tree["layers_2"]["mlp"]["router_bias"].shape == (64,)
    assert tree["layers_0"]["hc_attn"]["phi"].shape == (14336, 24)
    assert tree["hc_out"]["phi"].shape == (14336, 4)
    assert {leaf.dtype.name for leaf in jax.tree_util.tree_leaves(tree)} == {"bfloat16"}


def test_a_pair_costs_69_6_kflop_and_a_key_1152_bytes_on_the_memory_side_of_the_ridge():
    assert glue.pair_flops(CFG) == 2 * 32 * (576 + 512) == 69632
    one = glue.latent_decode_call(CFG, 1)
    assert one == {"bytes": 8 * 1152.0, "flops": 8 * 69632.0}
    assert one["flops"] / one["bytes"] == pytest.approx(60.4, abs=0.1)
    assert PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"] == pytest.approx(240.5, abs=0.1)


def test_latent_decode_call_by_hand():
    """32 rows at 13 k keys: 8 layers x 416000 keys x 1152 B = 3.83 GB of
    published rows (4.26 GB as the pool holds them), 4.68 ms at 819 GB/s."""
    keys = 32 * 13000
    call = glue.latent_decode_call(CFG, keys)
    assert call["bytes"] == 8 * keys * 1152 == pytest.approx(3.834e9, rel=1e-3)
    assert call["flops"] == 8 * keys * 69632
    assert call["bytes"] / 819e9 > call["flops"] / 197e12  # memory-bound


def test_latent_chunk_call_by_hand():
    """A 512-token tail chunk behind 12288 attached keys: 512 x 12288 +
    512 x 513 / 2 pairs a layer. At 32 heads a key's up-projection is
    2 x 512 x 32 x 256 = 8 388 608 FLOP and a pair 2 x 32 x 320 = 20 480
    against the absorbed 69 632: 1.91 TFLOP in 8 layers = 9.7 ms at peak
    (absorbed: 3.58 TFLOP, 18 ms). A 64-token tail has too few queries a key
    for the up-projection to pay, and counts absorbed."""
    call = glue.latent_chunk_call(CFG, 12288, 512)
    pairs = 512 * 12288 + 512 * 513 // 2
    assert 8 * pairs * 69632 == pytest.approx(3.58e12, rel=5e-3)
    up = 12800 * 8_388_608 + pairs * 20_480
    assert call["flops"] == 8 * up == pytest.approx(1.911e12, rel=1e-3)
    assert call["bytes"] == 8 * 12800 * 1152
    assert call["flops"] / 197e12 == pytest.approx(9.70e-3, rel=1e-2)
    short = glue.latent_chunk_call(CFG, 12288, 64)
    assert short["flops"] == 8 * (64 * 12288 + 64 * 65 // 2) * 69632


def test_the_streams_byte_count_by_hand():
    """A sublayer: read 4 streams, write u, read y, read 4 streams, write 4
    = 14 x 3584 values = 100352 B in bfloat16; 16 sublayers and the end's
    read of 4 and write of 1."""
    one = glue.hc_call(CFG, 1)
    assert one["bytes"] == 16 * 100352 + 5 * 3584 * 2 == 1_641_472
    assert (3 * 4 + 2) * 3584 * 2 == 100352
    assert glue.hc_call(CFG, 512)["bytes"] == 512 * 1_641_472
    assert glue.hc_call(CFG, 512)["bytes"] / 819e9 == pytest.approx(1.026e-3, rel=1e-3)
    assert one["flops"] == 16 * 2 * 14336 * 24 + 2 * 14336 * 4
    assert one["flops"] / one["bytes"] < 10  # far under the ridge: bytes bound it


# --- the reader ---------------------------------------------------------------

DEV = "/device:TPU:0"
PRE = "jit(prefill_chunk)/TransformerLM/layers_{}/hc_attn.pre/hc_pre/{}"
POST = "jit(prefill_chunk)/TransformerLM/layers_{}/hc_mlp/hc_post/{}"


def _chunk_runs(pid, n_runs, ops_a_run=6, each_ps=200_000_000, gap=40_000_000_000, t0=0):
    """`n_runs` runs of a chunk program, each holding `ops_a_run` operations
    under the maps' scopes and one of the mixer."""
    ops, runs = [], []
    for i in range(n_runs):
        start = t0 + i * gap
        runs.append(("jit_prefill_chunk", pid, start, gap - 1_000_000))
        for k in range(ops_a_run):
            path = (PRE if k % 2 else POST).format(k % 3, ("hc_mix/dot_general", "fusion")[k % 2])
            ops.append((path, pid, start + 1000 + k * 100_000_000, each_ps))
        ops.append(("jit(prefill_chunk)/TransformerLM/layers_0/latent_attn/q_up/dot_general",
                    pid, start + 900_000_000, 5_000_000))
    return ops, runs


def _env(config=CFG, name="no_such_trace_directory"):
    said = []
    return ReadEnv(cell={"config": config, "name": name}, samples={},
                   trace=xplane.Trace(devices={DEV: []}), peaks=PEAKS, chips=1,
                   memory_peak_bytes=0, say=said.append), said


def _reader(monkeypatch, sc, notes):
    from bench_matrix.readers import scope_time

    monkeypatch.setattr(scope_time, "_scopes", lambda env: sc)
    monkeypatch.setattr(latent_steps, "annotations", lambda env, name: notes)
    return hc_chunk_roofline


def test_the_reader_pairs_whole_runs_of_every_bucket_with_annotated_chunks(monkeypatch):
    args = spec.load("layer_metrics", "hc_chunk_roofline")["args"]
    big, big_runs = _chunk_runs(9, 2, ops_a_run=6)
    small, small_runs = _chunk_runs(11, 1, ops_a_run=4, t0=2 * 40_000_000_000)
    sc = scopes.Scopes(ops={DEV: sorted(big + small, key=lambda o: o[2])},
                       runs={DEV: big_runs + small_runs})
    notes = [{"slot": 1, "start": 12288, "tokens": 512, "bucket": 512},
             {"slot": 2, "start": 12288, "tokens": 400, "bucket": 512},
             {"slot": 1, "start": 12800, "tokens": 100, "bucket": 128}]
    env, said = _env()
    got = _reader(monkeypatch, sc, notes).read(args, env)
    need = sum(glue.hc_call(CFG, n["tokens"])["bytes"] for n in notes)
    assert got == pytest.approx(100 * (need / 819e9) / ((6 + 6 + 4) * 200e-6))
    assert 0 < got <= 100
    assert "3 dispatches kept, 3 runs" in said[-1] and "3 paired" in said[-1]
    assert "memory-bound" in said[-1]


def test_a_cut_run_is_not_whole_and_an_unpaired_end_is_dropped(monkeypatch):
    args = spec.load("layer_metrics", "hc_chunk_roofline")["args"]
    ops, runs = _chunk_runs(9, 3)
    notes = [{"slot": 1, "start": 12288, "tokens": 300 + i, "bucket": 512} for i in range(3)]
    # the last run lost two of its operations to `stop_trace`
    sc = scopes.Scopes(ops={DEV: sorted(ops[:-3] + ops[-1:], key=lambda o: o[2])},
                       runs={DEV: runs})
    env, said = _env()
    got = _reader(monkeypatch, sc, notes).read(args, env)
    need = sum(glue.hc_call(CFG, n["tokens"])["bytes"] for n in notes[:2])
    assert got == pytest.approx(100 * (need / 819e9) / (2 * 6 * 200e-6))
    assert "3 runs of the program in the slice, 2 of them whole, 2 paired" in said[-1]


def test_a_program_without_streams_or_a_run_without_annotations_gives_no_number(monkeypatch):
    """The parent commit's programs trace nothing under `hc_pre` / `hc_post`
    and its glues count no `hc_call`: the metric is left out, not 0, and
    nothing raises; with no trace file to read, the same."""
    args = spec.load("layer_metrics", "hc_chunk_roofline")["args"]
    bare = scopes.Scopes(
        ops={DEV: [("jit(prefill_chunk)/TransformerLM/layers_0/latent_attn/q_up/x", 9, 0, 1000)]},
        runs={DEV: [("jit_prefill_chunk", 9, 0, 2000)]})
    notes = [{"slot": 0, "start": 0, "tokens": 512, "bucket": 512}]
    assert _reader(monkeypatch, bare, notes).read(args, _env()[0]) is None
    ops, runs = _chunk_runs(9, 2)
    sc = scopes.Scopes(ops={DEV: ops}, runs={DEV: runs})
    assert _reader(monkeypatch, sc, []).read(args, _env()[0]) is None
    for other in ("openpangu-ultra-moe-718b-d7", "mistral-7b-v0.3-d16"):
        assert hc_chunk_roofline.read(args, _env(spec.load("configs", other))[0]) is None
    monkeypatch.undo()
    assert hc_chunk_roofline.read(args, _env()[0]) is None  # no trace file
    for name in NEW_METRICS[:3]:
        m = spec.load("layer_metrics", name)
        assert scopes.time_in(bare, m["args"]["program"], m["args"]["scope"]) in (None, 0.0)


def test_the_scope_metrics_read_both_halves_of_every_map_and_the_chain():
    step = "jit(step)/TransformerLM/"
    sc = scopes.Scopes(
        ops={DEV: [(step + "layers_1/hc_attn.pre/hc_pre/hc_mix/dot_general", 7, 0, 1_000_000_000),
                   (step + "layers_1/hc_attn.pre/hc_pre/hc_sinkhorn/div", 7, 2_000_000_000,
                    3_000_000_000),
                   (step + "layers_1/hc_post/add", 7, 6_000_000_000, 500_000_000),
                   (step + "hc_out.pre/hc_pre/mul", 7, 7_000_000_000, 250_000_000),
                   (step + "layers_1/latent_attn/q_up/dot_general", 7, 8_000_000_000, 9_000_000),
                   ("jit(prefill_chunk)/TransformerLM/layers_0/hc_mlp.pre/hc_pre/hc_sinkhorn/x",
                    9, 10_000_000_000, 4_000_000_000),
                   ("jit(prefill_chunk)/TransformerLM/layers_0/hc_post/fusion", 9,
                    15_000_000_000, 1_000_000_000)]},
        runs={DEV: [("jit_step", 7, 0, 9_000_000_000),
                    ("jit_prefill_chunk", 9, 10_000_000_000, 8_000_000_000)]})
    read = lambda name: scopes.time_in(
        sc, *(spec.load("layer_metrics", name)["args"][k] for k in ("program", "scope")))
    assert read("decode_hc_ms") == pytest.approx(4.75)
    assert read("decode_hc_sinkhorn_ms") == pytest.approx(3.0)
    assert read("prefill_hc_ms") == pytest.approx(5.0)


# --- the traffic mix and the cell ----------------------------------------------

def test_the_traffic_mix_is_the_cell_the_issue_names():
    t = spec.load("traffic", MIX)
    assert t["arrival"] == {"mode": "closed", "clients": 32, "ramp_seconds": 6.0}
    assert (t["shared_prefix_tokens"], t["prefix_groups"], t["strata"]) == (12288, 4, 64)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 12352, "median": 12832, "max": 13312}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 96, "sigma": 0.6,
                                  "min": 32, "max": 320}
    assert t["engine"] == {
        "block_size": 16, "pool_blocks": 16384, "prefill_chunk_tokens": 512,
        "max_seq_len": 16384, "min_bucket": 128, "kv_quant": False, "prefix_cache": True,
        "temperature": 0.0, "slots": 32}
    assert (t["warmup_seconds"], t["trace_seconds"], t["throughput_counts"]) == (14, 3, "generated")
    stream = traffic_gen.RequestStream(t, 131072, seed=2_500_000_011)
    heads = set()
    for _ in range(64):
        prompt, n_out = stream.next()
        assert 12352 <= len(prompt) <= 13312 and 32 <= n_out <= 320
        heads.add(prompt[:12288].tobytes())
    assert len(heads) == 4
    outs = traffic_gen.length_cycle(t["output_tokens"], 64)
    assert 90 <= np.median(outs) <= 102
    # the pool: 32 rows' own tails and decodes beside four heads, with room
    own = 32 * -(-(1024 + 320) // 16)
    assert 4 * 768 + own < 16384 and 13312 + 320 < 16384


def test_the_cell_reports_throughput_and_lists_what_the_issue_lists():
    cell = spec.load("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"], cell["runner"]) == (
        NAME, MIX, 1, "serve_prefix")
    assert cell["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    listed = cell["per_layer"]
    assert len(listed) == len(set(listed)) and set(NEW_METRICS) <= set(listed)
    # PR 35's step record runs here: its seven are listed, the four phase times too
    assert {"serve_host_work_ms", "serve_host_wait_pct", "serve_admit_ms", "serve_dispatch_ms",
            "serve_book_ms", "serve_queue_wait_ms", "prefix_hit_share"} <= set(listed)
    # PR 40: the whole window's share of the peak, and how many of a step's keys
    # the kernel reads once for several rows (0 while the latent kernel has no shared list)
    assert {"serve_mfu_pct", "shared_key_share"} <= set(listed)
    for gone in ("paged_decode_roofline", "moe_decode_roofline", "window_decode_roofline"):
        assert gone not in listed
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in listed:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] in ("serve_tokens_per_s", "setup_s")
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        f = spec.load("layer_metrics", name)
        assert {k: by_name[name][k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: f[k] for k in ("unit", "better", "source", "layer", "moves")}
    entry = [w for w in BENCH["workloads"] if w["name"] == CELL][0]
    assert entry == {"name": CELL, "config": NAME, "traffic": MIX, "chips": 1,
                     "why": cell["why"]}
    (config,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    assert config["reduced"] == ["num_hidden_layers"]
    c = cell["correctness"]
    assert (c["prompt_tokens"], c["attached_tokens"], c["last_positions"],
            c["decode_positions"]) == (12616, 12296, 256, 8)
    assert c["attached_tokens"] % 16 == 8  # the match ends inside a block


def test_the_cell_and_the_benchmark_list_each_other():
    """No test pins the TAIL of `BENCHMARK.json` any more (a later PR appends
    cells and metrics): what is held is membership, both ways. Every metric
    the cell's file lists names the cell in `BENCHMARK.json`, every metric
    there that names the cell is in the cell's file, and the cell, its
    configuration and its four metrics are in their lists once."""
    listed = spec.load("workloads", CELL)["per_layer"]
    naming = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    assert sorted(naming) == sorted(listed)
    assert [w["name"] for w in BENCH["workloads"]].count(CELL) == 1
    assert [c["name"] for c in BENCH["configs"]].count(NAME) == 1
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.count(n) == 1 for n in NEW_METRICS)
    assert "serve_sessions_prefix" in [w["name"] for w in BENCH["workloads"]]


def test_the_check_s_replay_has_the_shapes_of_the_engine_that_serves_the_configuration():
    replay = CFG["model"]["check"]["replay"]
    engine = spec.load("traffic", MIX)["engine"]
    for key in ("block_size", "prefill_chunk_tokens", "max_seq_len", "min_bucket", "slots"):
        assert replay[key] == engine[key], key
    c = spec.load("workloads", CELL)["correctness"]
    assert replay["decoded_tail"] == c["decode_positions"]
    assert replay["attached_tokens"] == c["attached_tokens"]
    cells = [w["name"] for w in BENCH["workloads"] if w["config"] == NAME]
    assert cells == [CELL]


TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=256, num_hidden_layers=3,
    first_k_dense_replace=1, n_routed_experts=8, num_experts_per_tok=2,
)


def _tiny_config(dtype="float32", attached=44):
    cfg = dict(CFG, **TINY, dtype={k: dtype for k in ("weights", "activations", "kv_cache")})
    cfg["published"] = dict(CFG["published"], n_routed_experts=8)
    cfg["rope_scaling"] = dict(CFG["rope_scaling"], factor=8, original_max_position_embeddings=32)
    cfg["model"] = dict(CFG["model"], check=dict(
        CFG["model"]["check"], tie_margin=0.05,
        replay={"block_size": 8, "prefill_chunk_tokens": 32, "max_seq_len": 128,
                "min_bucket": 16, "decoded_tail": 4, "slots": 4, "attached_tokens": attached}))
    return cfg


@pytest.mark.parametrize("attached", [0, 44, 64])
def test_the_replay_tells_the_experts_the_model_chose_behind_an_attached_head(attached):
    """A sequence of 75 + 4 tokens whose first `attached` were another
    request's: the holder's pass is told up to there (chunks from 0; the one
    that crosses 44 is whole), the rest from chunks that start at
    `attached` (44: 31 tokens in a bucket of 32; 64: 11 in one of 16), the
    decoded tail one token at a time as the step program calls the model (four
    rows, one live); every told choice, the decoded rows' too, is the
    cache-free model's."""
    import jax
    import jax.numpy as jnp

    from bench_matrix import modelglue

    cfg = _tiny_config(attached=attached)
    model = modelglue.build_model(cfg, 128, remat=False)
    variables = modelglue.make_variables(model, cfg, 5)
    layers = glue.reference_parts(variables)[1]
    tokens = traffic_gen.check_sequence(256, 9, 75 + 4)
    _, inter = jax.jit(lambda v, t: model.apply(v, t, mutable=["intermediates"]))(
        variables, jnp.asarray(tokens)[None])
    told = layers.system_routing(tokens, cfg)
    assert sorted(told) == [1, 2]
    for i, got in told.items():
        want = np.asarray(inter["intermediates"][f"layers_{i}"]["mlp"]["moe_chosen"][0][0])
        assert got.shape == (79, 2) and got.dtype == np.int32
        np.testing.assert_array_equal(np.sort(got, 1), np.sort(want, 1))
        assert (got >= 0).all()


def test_runner_gives_the_contract_line_for_the_cell_at_a_tiny_preset(capsys):
    """`run.execute` over the real cell's files with sizes cut in the test:
    bfloat16 as the cell runs, a head of 44 tokens attached (the match ends
    inside a block of 8), the prompt ending inside a bucket, the reference
    told the system's routing; `correct`, and only the cell's two end-to-end
    metrics."""
    import jax

    from _tiny import FAKE_PEAKS, context
    from bench_matrix import run

    cell = copy.deepcopy(spec.load_cell(CELL))
    cell["config"] = _tiny_config("bfloat16")
    t = cell["traffic"]
    t["engine"].update(block_size=8, pool_blocks=96, prefill_chunk_tokens=32,
                       max_seq_len=128, min_bucket=16, slots=4)
    t["arrival"].update(clients=4, ramp_seconds=0.2)
    t["prompt_tokens"].update(min=50, median=70, max=90)
    t["output_tokens"].update(median=6, min=3, max=12)
    t.update(strata=8, warmup_seconds=0.5, trace_seconds=0.5, shared_prefix_tokens=40,
             prefix_groups=2)
    cell["correctness"].update(prompt_tokens=75, attached_tokens=44, decode_positions=4,
                               last_positions=8, max_rel=0.5, rms_rel=0.15, chosen_gap=0.5)
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
    assert "44 tokens were attached" in said.out
    assert "choices told by the system" in said.err
