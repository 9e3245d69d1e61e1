"""The `openpangu-ultra-moe-718b-d7` configuration and what came with it: the
rule for a cut on its file, the glue's counts against the published sizes,
both latent roofline counts by hand, their readers' pairing of whole runs
with annotated dispatches, the traffic mix, the check's replay, and the cell
end to end at a tiny preset."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench_matrix import modelglue, spec, traffic_gen
from bench_matrix.glue import latent_moe as glue
from bench_matrix.readers import (
    ReadEnv, latent_chunk_roofline, latent_decode_roofline, latent_steps,
)
from bench_matrix.reduce import scopes, xplane

from test_bm_specs import check_cut

NAME, CELL = "openpangu-ultra-moe-718b-d7", "serve_pangu_longdoc_c8"
CFG = spec.load("configs", NAME)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


# --- the file ----------------------------------------------------------------

def test_the_file_holds_every_published_key_and_cuts_depth_and_experts_alone():
    check_cut(CFG)
    pub = CFG["published"]
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert (CFG["num_hidden_layers"], pub["num_hidden_layers"]) == (7, 61)
    assert (CFG["n_routed_experts"], pub["n_routed_experts"]) == (8, 256)
    for key, value in pub.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert (CFG["first_k_dense_replace"], CFG["num_experts_per_tok"], CFG["vocab_size"],
            CFG["num_nextn_predict_layers"]) == (3, 8, 153600, 1)
    assert [a[:3] for a in CFG["assumed"][:5]] == ["(1)", "(2)", "(3)", "(4)", "(5)"]
    assert "32 chips" in CFG["deployment"] and "experts 0-7" in CFG["deployment"]
    assert "MTP module" in CFG["deployment"] and "multi-token" in CFG["reduction_notes"]
    assert CFG["dtype"] == {"weights": "bfloat16", "activations": "bfloat16",
                            "logits": "float32", "router": "float32", "kv_cache": "bfloat16"}
    assert len(CFG["why"]) <= 200 and len(CFG["source"]) <= 200


def test_the_file_s_published_keys_are_the_catalog_row_s():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    (row,) = [r for r in rows if r["name"] == "openPangu-Ultra-MoE-718B"]
    assert CFG["published"] == row["config"] and CFG["source"] == row["source_url"]


def test_the_assumptions_stand_in_the_reference_in_the_same_words():
    text = Path(spec.ROOT / "reference" / "latent_moe.py").read_text()
    flat = " ".join(text.split())
    for words in ("no groups and no correction bias", "ADJACENT pairs (value 2j with value 2j + 1)",
                  "no mscale", "c_kv is cached after its norm", "the four norms above and no other"):
        assert words in flat, words
    said = " ".join(CFG["assumed"])
    for words in ("no groups and no correction bias", "ADJACENT pairs (value 2j with value 2j + 1)",
                  "no mscale", "c_kv is cached AFTER its RMSNorm", "four norms a block and no other"):
        assert words in said, words
    assert "import pytorch_distributed_example_tpu" not in text
    assert "from pytorch_distributed_example_tpu" not in text


REFUSED = {
    "an_eighth_layer_is_fine_six_are_not": ({"num_hidden_layers": 6}, "under the floor of 7"),
    "two_dense_layers": ({"first_k_dense_replace": 2}, "exactly the keys that differ"),
    "sixteen_experts_over_32_chips": ({"n_routed_experts": 16}, "share of one chip"),
    "four_experts": ({"n_routed_experts": 4}, "routed experts"),
    "a_narrower_latent": (
        {"kv_lora_rank": 256, "reduced": CFG["reduced"] + ["kv_lora_rank"]}, "must equal"),
    "fewer_heads": (
        {"num_attention_heads": 64, "reduced": CFG["reduced"] + ["num_attention_heads"]},
        "must equal"),
    "four_experts_a_token": (
        {"num_experts_per_tok": 4, "reduced": CFG["reduced"] + ["num_experts_per_tok"]},
        "must equal"),
    "half_the_vocabulary_beside_an_expert_share_of_32": (
        {"vocab_size": 76800, "reduced": CFG["reduced"] + ["vocab_size"]}, "share of one chip"),
    "a_share_with_no_deployment": ({"deployment": "one chip"}, "number of chips"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_rule_for_a_cut_refuses(case):
    change, word = REFUSED[case]
    with pytest.raises(AssertionError, match=word):
        check_cut(dict(CFG, **change))


# --- the glue's counts --------------------------------------------------------

def test_the_glue_counts_the_cut_and_the_published_model():
    """ISSUE 33's arithmetic: 196.58 M an attention block, 621.28 M a dense
    layer, 245.7 M a sparse layer outside its experts of 47.19 M each,
    2359.3 M in embedding and head; 6716.1 M held, 719.09 B published."""
    d = 7680
    attn = (d * 1536 + 1536 + 1536 * 128 * 192 + d * 576 + 512 + 512 * 128 * 256
            + 128 * 128 * d)
    assert glue.attention_params(CFG) == attn == 196_577_280
    assert glue.expert_params(CFG) == 3 * d * 2048 == 47_185_920
    dense = attn + 4 * d + 3 * d * 18432
    assert glue.layer_params(CFG, 0) == glue.layer_params(CFG, 2) == dense
    assert dense == pytest.approx(621.28e6, rel=1e-5)
    outside = attn + 4 * d + d * 256 + 47_185_920
    assert outside == pytest.approx(245.7e6, rel=1e-3)
    assert glue.layer_params(CFG, 3) == outside + 8 * 47_185_920
    assert glue.layer_params(CFG, 3) == pytest.approx(623.25e6, rel=1e-5)
    assert glue.layer_params(CFG["published"], 3) == outside + 256 * 47_185_920
    assert glue.layer_params(CFG, 3, active=True) == glue.layer_params(CFG, 6, active=True)
    assert 2 * 153600 * d == pytest.approx(2359.3e6, rel=1e-4)
    assert glue.param_count(CFG) == 3 * dense + 4 * (outside + 8 * 47_185_920) + d + 2 * 153600 * d
    assert glue.param_count(CFG) == pytest.approx(6716.1e6, rel=1e-5)
    assert glue.param_count(CFG["published"]) == pytest.approx(719.09e9, rel=1e-5)
    assert 2 * glue.param_count(CFG) == pytest.approx(13.43e9, rel=1e-3)  # bfloat16


def test_the_program_holds_what_the_glue_counts():
    """The model's own parameter tree at the published widths, by shape."""
    import jax

    from bench_matrix import modelglue

    model = modelglue.build_model(CFG, 16384, remat=False)
    shapes = jax.eval_shape(modelglue.init_fn(model, CFG), jax.random.PRNGKey(0))["params"]
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert held == glue.param_count(CFG)
    assert {a.dtype.name for a in jax.tree_util.tree_leaves(shapes)} == {"bfloat16"}
    assert shapes["layers_3"]["mlp"]["router"].shape == (7680, 256)
    assert shapes["layers_3"]["mlp"]["experts_gate"].shape == (8, 7680, 2048)
    assert "router" not in shapes["layers_2"]["mlp"]


def test_training_flops_count_active_parameters_and_the_keys_as_a_trainer_attends_them():
    seq = 4096
    matmuls = sum(glue.layer_params(CFG, i, active=True) for i in range(7)) + 7680 * 153600
    attention = 7 * 2 * 128 * (128 + 64 + 128) * (seq + 1) / 2
    assert glue.train_flops_per_token(CFG, seq) == pytest.approx(3 * (2 * matmuls + attention))


# --- the two roofline counts, by hand -----------------------------------------

def test_a_pair_costs_278_5_kflop_and_a_key_1152_bytes_on_the_v5e_s_ridge():
    assert glue.pair_flops(CFG) == 2 * 128 * (576 + 512) == 278_528
    one = glue.latent_decode_call(CFG, 1)
    assert one == {"bytes": 7.0 * 1152, "flops": 7.0 * 278_528}
    assert one["flops"] / one["bytes"] == pytest.approx(241.8, abs=0.1)
    assert 197e12 / 819e9 == pytest.approx(240.5, abs=0.1)


def test_latent_decode_call_by_hand():
    """Three rows that attend 700, 1200 and 90 keys: every key's published
    576 values read once a layer, whatever the pool's rows hold."""
    got = glue.latent_decode_call(CFG, 700 + 1200 + 90)
    assert got["bytes"] == 7 * 1990 * 576 * 2 and got["flops"] == 7 * 1990 * 278_528
    # rows behind one head of 512 keys: its rows are read once, every pair is computed
    shared = glue.latent_decode_call(CFG, 700 + 1200 + 90, distinct=1990 - 2 * 90)
    assert shared["bytes"] == 7 * 1810 * 576 * 2 and shared["flops"] == got["flops"]
    assert glue.latent_decode_call(CFG, 0) == {"bytes": 0.0, "flops": 0.0}
    # the whole pool once: ISSUE 33's 1.06 GB, 1.3 ms at 819 GB/s
    full = glue.latent_decode_call(CFG, 8 * 16384)
    assert full["bytes"] == pytest.approx(1.057e9, rel=1e-3)
    assert full["bytes"] / 819e9 == pytest.approx(1.29e-3, rel=1e-2)


def test_latent_chunk_call_by_hand():
    """512 real tokens from position 4096: token t attends 4096 + t + 1 keys;
    the 4608 keys are read once a chunk and a layer. The FLOPs are the
    cheaper form's at the chunk's own size: absorbed, 278 528 a pair; or each
    of the 4608 keys' 128 heads up-projected once a chunk (2 x 512 x 128 x
    (128 + 128) = 33 554 432 a key) and a pair at 2 x 128 x (128 + 64 + 128) =
    81 920: ROADMAP S14's 147 kFLOP a pair at a long context."""
    got = glue.latent_chunk_call(CFG, 4096, 512)
    pairs = sum(4096 + t + 1 for t in range(512))
    assert pairs == 512 * 4096 + 512 * 513 // 2
    up = 4608 * 33_554_432 + pairs * 81_920
    assert up < pairs * 278_528 and glue.chunk_pair_flops(CFG, 4608, pairs) == up
    assert got["flops"] == 7 * up and got["bytes"] == 7 * 4608 * 1152
    assert up / pairs == pytest.approx(151.3e3, rel=1e-3)
    assert 33_554_432 / 512 + 81_920 == 147_456  # as the context grows
    # ISSUE 33's chunk at ~5 k keys a query: 2.8e12 FLOP where the absorbed form has 5.1e12
    assert glue.latent_chunk_call(CFG, 4864, 512)["flops"] == pytest.approx(2.79e12, rel=1e-2)
    # a last chunk of 416 tokens in a bucket of 512 counts its 416
    short = glue.latent_chunk_call(CFG, 3584, 416)
    assert short["flops"] == 7 * (4000 * 33_554_432 + (416 * 3584 + 416 * 417 // 2) * 81_920)
    # few queries a key: up-projecting costs more than it saves, absorbed counts
    assert glue.latent_chunk_call(CFG, 0, 1) == {"bytes": 7.0 * 1152, "flops": 7.0 * 278_528}
    tail = glue.latent_chunk_call(CFG, 4096, 100)
    assert tail["flops"] == 7 * (100 * 4096 + 100 * 101 // 2) * 278_528
    # compute-bound by two orders of magnitude
    assert got["flops"] / 197e12 > 100 * got["bytes"] / 819e9


# --- the readers --------------------------------------------------------------

DEV = "/device:TPU:0"
STEP = "jit(step)/TransformerLM/layers_{}/latent_attn/cache_attention/latent_decode_kernel/pallas_call"
CHUNK = ("jit(prefill_chunk)/TransformerLM/layers_{}/latent_attn/cache_attention/"
         "latent_chunk_kernel/pallas_call")


def _runs(program, pid, path, n_runs, calls=7, each_ps=2_000_000, gap=40_000_000, t0=0):
    """`n_runs` runs of a program, each holding `calls` kernel operations and
    one other operation of the mixer."""
    ops, runs = [], []
    for i in range(n_runs):
        start = t0 + i * gap
        runs.append((program, pid, start, gap - 1_000_000))
        for k in range(calls):
            ops.append((path.format(k), pid, start + 1000 + k * 3_000_000, each_ps))
        ops.append((path.format(0).replace("cache_attention", "q_up").rsplit("/", 2)[0]
                    + "/dot_general", pid, start + 30_000_000, 5_000_000))
    return ops, runs


def _env(config=CFG, name="no_such_trace_directory"):
    said = []
    return ReadEnv(cell={"config": config, "name": name}, samples={},
                   trace=xplane.Trace(devices={DEV: []}), peaks=PEAKS, chips=1,
                   memory_peak_bytes=0, say=said.append), said


def _reader(monkeypatch, module, sc, notes):
    from bench_matrix.readers import scope_time

    monkeypatch.setattr(scope_time, "_scopes", lambda env: sc)
    monkeypatch.setattr(latent_steps, "annotations", lambda env, name: notes)
    return module


def _kept(env, notes, shared=0):
    """The runner's record of the decode steps it dispatched: `rows` rows of
    `keys` keys in all, `shared` of them in blocks another row holds too."""
    env.samples["decode_steps"] = [
        {"keys": [n["keys"] // n["rows"]] * (n["rows"] - 1)
                 + [n["keys"] - n["keys"] // n["rows"] * (n["rows"] - 1)],
         "distinct": n["keys"] - shared} for n in notes]
    return env


def test_the_decode_reader_pairs_whole_runs_with_annotated_steps(monkeypatch):
    args = spec.load("layer_metrics", "latent_decode_roofline")["args"]
    ops, runs = _runs("jit_step", 7, STEP, 3)
    sc = scopes.Scopes(ops={DEV: sorted(ops, key=lambda o: o[2])}, runs={DEV: runs})
    notes = [{"rows": 3, "keys": 2000}, {"rows": 3, "keys": 2003}, {"rows": 2, "keys": 1500}]
    env, said = _env()
    got = _reader(monkeypatch, latent_decode_roofline, sc, None).read(args, _kept(env, notes))
    need = sum(glue.latent_decode_call(CFG, n["keys"])["flops"] for n in notes)
    assert got == pytest.approx(100 * (need / 197e12) / (3 * 7 * 2e-6))
    assert "3 dispatches kept, 3 runs" in said[-1] and "3 paired" in said[-1]
    assert "compute-bound" in said[-1]
    # rows behind one head: fewer bytes, the same FLOPs, and the FLOPs bound it
    env, said = _env()
    assert latent_decode_roofline.read(args, _kept(env, notes, shared=900)) == pytest.approx(got)
    assert "compute-bound" in said[-1] and f"{7 * (5503 - 2700) * 1152:.3e} bytes" in said[-1]


def test_a_slice_whose_first_and_last_step_are_cut_still_gives_a_number(monkeypatch):
    """The last dispatch's run was cut by `stop_trace` (5 of its 7 calls, no
    event on the modules line) and one run in the slice was dispatched
    before it began: whole runs pair with annotations, an unpaired end is
    dropped, both counts are said; never 0."""
    args = spec.load("layer_metrics", "latent_decode_roofline")["args"]
    ops, runs = _runs("jit_step", 7, STEP, 4)
    cut = [(STEP.format(k), 7, 4 * 40_000_000 + k * 3_000_000, 2_000_000) for k in range(5)]
    sc = scopes.Scopes(ops={DEV: sorted(ops + cut, key=lambda o: o[2])}, runs={DEV: runs})
    notes = [{"rows": 2, "keys": 1000 + i} for i in range(5)]  # the fifth's run was cut
    env, said = _env()
    got = _reader(monkeypatch, latent_decode_roofline, sc, None).read(args, _kept(env, notes))
    need = sum(glue.latent_decode_call(CFG, n["keys"])["flops"] for n in notes[:4])
    assert got == pytest.approx(100 * (need / 197e12) / (4 * 7 * 2e-6))
    assert "5 dispatches kept, 4 runs of the program in the slice, 4 of them whole, 4 paired" in said[-1]
    # a run on the modules line that lacks a call is not whole
    short = scopes.Scopes(ops={DEV: sorted(ops[:-4], key=lambda o: o[2])}, runs={DEV: runs})
    env, said = _env()
    _reader(monkeypatch, latent_decode_roofline, short, None).read(args, _kept(env, notes[:4]))
    assert "4 runs of the program in the slice, 3 of them whole, 3 paired" in said[-1]
    # more whole runs than annotations: the leading run was dispatched before the slice
    env, said = _env()
    got = _reader(monkeypatch, latent_decode_roofline, sc, None).read(args, _kept(env, notes[:3]))
    need = sum(glue.latent_decode_call(CFG, n["keys"])["flops"] for n in notes[:3])
    assert got == pytest.approx(100 * (need / 197e12) / (3 * 7 * 2e-6))
    assert 0 < got <= 100


def test_the_chunk_reader_counts_real_tokens_over_every_bucket_s_program(monkeypatch):
    args = spec.load("layer_metrics", "latent_chunk_roofline")["args"]
    big, big_runs = _runs("jit_prefill_chunk", 9, CHUNK, 2, each_ps=3_000_000_000,
                          gap=60_000_000_000)
    small, small_runs = _runs("jit_prefill_chunk", 11, CHUNK, 1, each_ps=2_000_000_000,
                              gap=60_000_000_000, t0=2 * 60_000_000_000)
    step, step_runs = _runs("jit_step", 7, STEP, 2, t0=4 * 60_000_000_000)
    sc = scopes.Scopes(ops={DEV: sorted(big + small + step, key=lambda o: o[2])},
                       runs={DEV: big_runs + small_runs + step_runs})
    notes = [{"slot": 1, "start": 4096, "tokens": 512, "bucket": 512},
             {"slot": 1, "start": 4608, "tokens": 512, "bucket": 512},
             {"slot": 1, "start": 5120, "tokens": 100, "bucket": 128}]
    env, said = _env()
    got = _reader(monkeypatch, latent_chunk_roofline, sc, notes).read(args, env)
    need = sum(glue.latent_chunk_call(CFG, n["start"], n["tokens"])["flops"] for n in notes)
    assert got == pytest.approx(100 * (need / 197e12) / (7 * (2 * 3e-3 + 2e-3)))
    assert "3 paired" in said[-1] and "compute-bound" in said[-1]


def test_a_program_without_the_scope_or_a_run_without_annotations_gives_no_number(monkeypatch):
    """The parent commit's programs trace nothing under the kernels' scopes
    and write no such annotation: the metric is left out, not 0, and nothing
    raises; with no trace file to read, or a glue without the count, the
    same."""
    dec = spec.load("layer_metrics", "latent_decode_roofline")["args"]
    chk = spec.load("layer_metrics", "latent_chunk_roofline")["args"]
    bare = scopes.Scopes(
        ops={DEV: [("jit(step)/TransformerLM/layers_0/attn/cache_attention/x", 7, 0, 1000)]},
        runs={DEV: [("jit_step", 7, 0, 2000)]})
    notes = [{"rows": 1, "keys": 5}]
    assert _reader(monkeypatch, latent_decode_roofline, bare, notes).read(
        dec, _kept(_env()[0], notes)) is None
    assert _reader(monkeypatch, latent_chunk_roofline, bare, notes).read(chk, _env()[0]) is None
    ops, runs = _runs("jit_step", 7, STEP, 2)
    sc = scopes.Scopes(ops={DEV: ops}, runs={DEV: runs})
    assert _reader(monkeypatch, latent_decode_roofline, sc, []).read(dec, _env()[0]) is None
    assert _reader(monkeypatch, latent_chunk_roofline, sc, []).read(chk, _env()[0]) is None
    other = spec.load("configs", "mistral-7b-v0.3-d16")
    assert latent_decode_roofline.read(dec, _env(other)[0]) is None
    assert latent_chunk_roofline.read(chk, _env(other)[0]) is None
    monkeypatch.undo()
    assert latent_decode_roofline.read(dec, _kept(_env()[0], notes)) is None  # no trace file
    assert latent_chunk_roofline.read(chk, _env()[0]) is None
    for name in ("decode_latent_attention_ms", "prefill_latent_attention_ms"):
        m = spec.load("layer_metrics", name)
        assert scopes.time_in(bare, m["args"]["program"], m["args"]["scope"]) in (None, 0.0)


def test_the_scope_metrics_read_the_mixer_and_the_kernel_s_scope():
    base = "jit(step)/TransformerLM/layers_1/latent_attn/"
    chunk = "jit(prefill_chunk)/TransformerLM/layers_1/latent_attn/"
    sc = scopes.Scopes(
        ops={DEV: [(base + "q_up/q_b_proj/dot_general", 7, 0, 1_000_000_000),
                   (base + "cache_attention/jit(_latent_decode_device)/latent_decode_kernel/pallas_call",
                    7, 2_000_000_000, 3_000_000_000),
                   (base + "absorb_out/dot_general", 7, 6_000_000_000, 500_000_000),
                   ("jit(step)/TransformerLM/layers_1/mlp/moe/experts/x", 7, 7_000_000_000,
                    250_000_000),
                   (chunk + "cache_attention/jit(_latent_chunk_device)/latent_chunk_kernel/pallas_call",
                    9, 10_000_000_000, 4_000_000_000),
                   (chunk + "absorb_q/dot_general", 9, 15_000_000_000, 1_000_000_000)]},
        runs={DEV: [("jit_step", 7, 0, 8_000_000_000),
                    ("jit_prefill_chunk", 9, 10_000_000_000, 8_000_000_000)]})
    read = lambda name: scopes.time_in(
        sc, *(spec.load("layer_metrics", name)["args"][k] for k in ("program", "scope")))
    assert read("decode_latent_attention_ms") == pytest.approx(4.5)
    assert read("decode_cache_attention_ms") == pytest.approx(3.0)
    assert read("prefill_latent_attention_ms") == pytest.approx(5.0)
    assert read("prefill_latent_cache_attention_ms") == pytest.approx(4.0)
    assert read("decode_moe_ms") == pytest.approx(0.25)
    per_device = latent_steps.kernel_seconds(
        sc, "^jit_step$", spec.load("layer_metrics", "latent_decode_roofline")["args"]["scope"], 1)
    assert per_device == [(DEV, [3e-3], 1)]


# --- the traffic mix and the cell ----------------------------------------------

def test_the_traffic_mix_is_the_cell_the_issue_names():
    t = spec.load("traffic", "longdoc_closed_c8_16k")
    assert t["arrival"] == {"mode": "closed", "clients": 8, "ramp_seconds": 4.0}
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 8192, "sigma": 0.5,
                                  "min": 2048, "max": 16000}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 64, "sigma": 0.5,
                                  "min": 16, "max": 128}
    assert t["engine"] == {
        "block_size": 16, "pool_blocks": 8192, "prefill_chunk_tokens": 512,
        "max_seq_len": 16384, "min_bucket": 128, "kv_quant": False, "prefix_cache": False,
        "temperature": 0.0, "slots": 8}
    assert (t["strata"], t["shared_prefix_tokens"], t["warmup_seconds"],
            t["trace_seconds"], t["throughput_counts"]) == (16, 0, 6, 3, "all")
    prompts = traffic_gen.length_cycle(t["prompt_tokens"], t["strata"])
    outputs = traffic_gen.length_cycle(t["output_tokens"], t["strata"])
    assert 2048 <= prompts.min() and prompts.max() <= 16000
    assert 16 <= outputs.min() and outputs.max() <= 128
    assert prompts.max() + outputs.max() <= t["engine"]["max_seq_len"]
    assert 8500 < prompts.mean() < 9800
    # the pool that preempts nothing: every slot at the sequence limit
    eng = t["engine"]
    assert eng["pool_blocks"] * eng["block_size"] == eng["slots"] * eng["max_seq_len"]
    # as held: 8192 blocks x 16 tokens x 640 values x 2 bytes x 7 layers
    assert eng["pool_blocks"] * 16 * 640 * 2 * 7 == pytest.approx(1.174e9, rel=1e-3)


def test_the_cell_reports_throughput_and_lists_only_what_moves_what_it_reports():
    cell = spec.load_cell(CELL)
    assert cell["config_name"] == NAME and cell["chips"] == 1 and cell["runner"] == "serve"
    assert list(cell["end_to_end"]) == ["serve_tokens_per_s", "setup_s"]
    assert {m["moves"] for m in cell["per_layer"].values()} == {"serve_tokens_per_s", "setup_s"}
    new = {"decode_latent_attention_ms", "prefill_latent_attention_ms",
           "prefill_latent_cache_attention_ms", "latent_decode_roofline",
           "latent_chunk_roofline"}
    assert new | {"serve_mfu_pct"} <= set(cell["per_layer"]) and len(cell["per_layer"]) == 17
    assert not {"moe_decode_roofline", "paged_decode_roofline"} & set(cell["per_layer"])
    for name in new:
        m = cell["per_layer"][name]
        roof = name.endswith("_roofline")
        assert m["reader"] == (name if roof else "scope_ms")
        assert (m["unit"], m["better"]) == (("%", "higher") if roof else ("ms", "lower"))
        assert m["layer"] == ("kernels" if roof else "model")
        listed = next(x for x in BENCH["per_layer"] if x["name"] == name)
        assert CELL in listed["workloads"]
        # every cell that lists one of the five runs a latent configuration
        for other in listed["workloads"]:
            assert hasattr(modelglue.glue(spec.load_cell(other)["config"]), "latent_decode_call")
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
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=256, num_hidden_layers=3,
    first_k_dense_replace=1, n_routed_experts=8, num_experts_per_tok=2,
)


def _tiny_config(dtype="float32"):
    cfg = dict(CFG, **TINY, dtype={k: dtype for k in ("weights", "activations", "kv_cache")})
    cfg["published"] = dict(CFG["published"], n_routed_experts=32)
    cfg["model"] = dict(CFG["model"], check=dict(
        CFG["model"]["check"], tie_margin=0.05,
        replay={"block_size": 8, "prefill_chunk_tokens": 32, "max_seq_len": 128,
                "min_bucket": 16, "decoded_tail": 4}))
    return cfg


def test_the_replay_tells_the_experts_the_model_chose_in_the_padded_last_chunk_too():
    """A sequence of 64 + 4 tokens: the prompt's 64 go in two whole chunks;
    one of 75 + 4 ends inside a bucket (32, 32, 11 in a bucket of 16): the
    last chunk is padded as the engine pads it and its rows are told too; the
    decoded tail keeps -1. 8 of 32 experts held: the router's choices range
    over all 32."""
    import jax
    import jax.numpy as jnp

    from bench_matrix import modelglue

    cfg = _tiny_config()
    model = modelglue.build_model(cfg, 128, remat=False)
    variables = modelglue.make_variables(model, cfg, 5)
    layers = glue.reference_parts(variables)[1]
    for n_prompt in (64, 75):
        tokens = traffic_gen.check_sequence(256, 9, n_prompt + 4)
        _, inter = jax.jit(lambda v, t: model.apply(v, t, mutable=["intermediates"]))(
            variables, jnp.asarray(tokens)[None])
        told = layers.system_routing(tokens, cfg)
        assert sorted(told) == [1, 2]
        for i, got in told.items():
            want = np.asarray(inter["intermediates"][f"layers_{i}"]["mlp"]["moe_chosen"][0][0])
            assert got.shape == (n_prompt + 4, 2) and got.dtype == np.int32
            np.testing.assert_array_equal(np.sort(got[:n_prompt], 1), np.sort(want[:n_prompt], 1))
            assert (got[n_prompt:] == -1).all() and got[:n_prompt].max() >= 8


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


def test_the_annotations_of_a_traced_engine_say_every_dispatch(tmp_path, monkeypatch):
    """A tiny engine traced on the CPU: one `serve:prefill_chunk` a chunk
    (slot, start, tokens, bucket) and one `serve:decode_step` a step (rows,
    keys attended: each row's cached keys and the one the step writes) on
    the host line, in dispatch order; the engine reads none of them back."""
    import jax

    from bench_matrix import modelglue, run
    from pytorch_distributed_example_tpu.serve import ServeEngine

    cfg = _tiny_config()
    model = modelglue.build_model(cfg, 128, remat=False)
    engine = ServeEngine(model, modelglue.make_variables(model, cfg, 3), slots=2,
                         block_size=8, pool_blocks=32, prefill_chunk_tokens=32, min_bucket=16)
    engine.submit(np.arange(9, dtype=np.int32), 3, rid="warm")
    engine.run()
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path / "trace" / "tiny"), profiler_options=opts)
    try:
        engine.submit(np.arange(75, dtype=np.int32) % 256, 5, rid="a")
        engine.submit(np.arange(20, dtype=np.int32), 4, rid="b")
        done = engine.run(max_steps=50)
    finally:
        jax.profiler.stop_trace()
    env, _ = _env(cfg, "tiny")
    chunks = latent_steps.annotations(env, "serve:prefill_chunk")
    steps = latent_steps.annotations(env, "serve:decode_step")
    by_slot = {}
    for c in chunks:
        by_slot.setdefault(c["slot"], []).append((c["start"], c["tokens"], c["bucket"]))
    assert sorted(by_slot.values()) == [[(0, 20, 32)], [(0, 32, 32), (32, 32, 32), (64, 11, 16)]]
    assert len(steps) == engine.metrics.decode_steps - 2  # the warm request's two
    assert all(1 <= s["rows"] <= 2 and s["keys"] >= 21 for s in steps)
    # a step's keys grow by its rows while the same rows decode
    assert any(b["keys"] - a["keys"] == a["rows"] == b["rows"] for a, b in zip(steps, steps[1:]))
    assert len(done["a"].tokens) == 5 and len(done["b"].tokens) == 4
    # a CPU trace has no device plane: no time to read, no number
    for name, reader in (("latent_decode_roofline", latent_decode_roofline),
                         ("latent_chunk_roofline", latent_chunk_roofline)):
        assert reader.read(spec.load("layer_metrics", name)["args"], env) is None
