"""The `jax.named_scope`s the per-layer metrics of `bench_matrix` read sit
where the metric files say: the paged decode step, a prefill chunk and one
step of each trainer are lowered at a tiny size and every scope must appear
under its Flax path. A scope changes HLO metadata only, so nothing else
notices when a refactor drops one and a metric silently reads nothing.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import optax
import pytest

import pytorch_distributed_example_tpu as tdx
from pytorch_distributed_example_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
)

# Flax names a method other than __call__ `<module>.<method>` in the path
LAYER = r"TransformerLM\)*/layers_\d+/attn/(attn\.\w+/)*"
MLP = r"TransformerLM\)*/layers_\d+/mlp/"
LINEAR = r"TransformerLM\)*/layers_\d+/linear_attn/"
LATENT = r"TransformerLM\)*/layers_\d+/latent_attn/(latent_attn\.\w+/)*"
HC = r"TransformerLM\)*/layers_\d+/hc_(attn|mlp)\.pre/"
CONV = r"TransformerLM\)*/layers_\d+/gated_conv/"
# program -> scope -> where it must appear (a regex on the whole path)
EXPECTED = {
    "step": {
        "rope": LAYER + r"rope/",
        "kv_scatter": LAYER + r"kv_scatter/scatter",
        "kv_gather": LAYER + r"kv_gather/",
        "cache_attention": LAYER + r"cache_attention/.*dot_general",
        "sample": r"^jit\(step\)/sample/",
    },
    # the same program at a head size the decode kernel takes (Dh = 128):
    # every layer calls the kernel's one jitted body under cache_attention
    # (the compiler inlines it, so on the chip the custom call's path is
    # `.../cache_attention/jit(_per_device)/paged_decode_attention/
    # pallas_call`), and nothing is gathered
    "step_kernel": {
        "rope": LAYER + r"rope/",
        "kv_scatter": LAYER + r"kv_scatter/scatter",
        "cache_attention": LAYER + r"cache_attention/jit\(_per_device\)$",
        "kernel_body": r"^paged_decode_attention/",
        "sample": r"^jit\(step\)/sample/",
    },
    "prefill_chunk": {
        "rope": LAYER + r"rope/",
        "kv_scatter": LAYER + r"kv_scatter/scatter",
        "kv_gather": LAYER + r"kv_gather/",
        "cache_attention": LAYER + r"cache_attention/.*dot_general",
    },
    "first_token": {"sample": r"^jit\(first_token\)/sample/"},
    # a model with a layer pattern (window and full attention, a gate on the
    # attention output, dropless sparse MLPs with a shared expert), at a
    # small size: every scope its per-layer metrics read
    "pattern_step": {
        "moe": MLP + r"moe/",
        "router": MLP + r"moe/router/dot_general",
        "dispatch": MLP + r"moe/dispatch/",
        "experts": MLP + r"moe/experts/ragged_dot",
        "shared_expert": MLP + r"moe/shared_expert/(gate|up|down)_proj/dot_general",
        "attn_gate": LAYER + r"attn_gate/",
        "window_attention": LAYER + r"window_attention/cache_attention/.*dot_general",
        "window_gather": LAYER + r"window_attention/kv_gather/",
        "cache_attention": LAYER + r"cache_attention/.*dot_general",
        "rope": LAYER + r"rope/",
        "sample": r"^jit\(step\)/sample/",
    },
    "pattern_prefill_chunk": {
        "moe": MLP + r"moe/",
        "experts": MLP + r"moe/experts/ragged_dot",
        "shared_expert": MLP + r"moe/shared_expert/",
        "attn_gate": LAYER + r"attn_gate/",
        "window_attention": LAYER + r"window_attention/cache_attention/.*dot_general",
        "kv_scatter": LAYER + r"kv_scatter/scatter",
    },
    # the same model at a head size the decode kernel takes: a window
    # layer's call sits under window_attention/cache_attention
    "pattern_step_kernel": {
        "window_attention":
            LAYER + r"window_attention/cache_attention/jit\(_per_device\)$",
        "cache_attention": LAYER + r"cache_attention/jit\(_per_device\)$",
    },
    # a model that mixes linear-attention layers with full attention: the
    # decode step runs the recurrence, a prefill chunk the chunked scan, and
    # each sits under the mixer's Flax name with the conv and the gated norm
    "hybrid_step": {
        "linear_attn": LINEAR + r"(q|k|v|g|o|a|b)_proj/dot_general",
        "short_conv": LINEAR + r"short_conv/",
        "recurrence": LINEAR + r"recurrence/",
        "state_read": LINEAR + r"recurrence/jit\(_take\)",
        "state_write": LINEAR + r"recurrence/scatter",
        "gated_norm": LINEAR + r"gated_norm/",
        "cache_attention": LAYER + r"cache_attention/.*dot_general",
        "sample": r"^jit\(step\)/sample/",
    },
    # the same model at widths the decode kernel of the recurrence takes:
    # every linear layer calls the kernel's one jitted body under recurrence
    # (on the chip `.../recurrence/jit(_call)/paged_delta_step/pallas_call`),
    # and its state is neither gathered nor scattered by XLA
    "hybrid_step_kernel": {
        "recurrence": LINEAR + r"recurrence/jit\(_call\)$",
        "kernel_body": r"^paged_delta_step/",
        "tail_write": LINEAR + r"recurrence/scatter",
    },
    "hybrid_prefill_chunk": {
        "short_conv": LINEAR + r"short_conv/",
        "chunk_scan": LINEAR + r"chunk_scan/.*dot_general",
        "state_write": LINEAR + r"chunk_scan/scatter",
        "gated_norm": LINEAR + r"gated_norm/",
        "kv_scatter": LAYER + r"kv_scatter/scatter",
    },
    # a model of latent-attention layers with sandwich norms and a sigmoid
    # router: the mixer's seven scopes under its Flax name, the cached
    # attention under `cache_attention` (which the listed
    # `decode_cache_attention_ms` reads), dense here
    "latent_step": {
        "q_down": LATENT + r"q_down/q_a_proj/dot_general",
        "q_up": LATENT + r"q_up/q_b_proj/dot_general",
        "kv_down": LATENT + r"kv_down/kv_a_proj/dot_general",
        "absorb_q": LATENT + r"absorb_q/.*dot_general",
        "absorb_out": LATENT + r"absorb_out/.*dot_general",
        "rope": LATENT + r"rope/",
        "kv_scatter": LATENT + r"kv_scatter/scatter",
        "kv_gather": LATENT + r"cache_attention/kv_gather/",
        "cache_attention": LATENT + r"cache_attention/.*dot_general",
        "moe": MLP + r"moe/router/logistic",
        "sample": r"^jit\(step\)/sample/",
    },
    "latent_prefill_chunk": {
        "absorb_q": LATENT + r"absorb_q/.*dot_general",
        "absorb_out": LATENT + r"absorb_out/.*dot_general",
        "kv_scatter": LATENT + r"kv_scatter/scatter",
        "cache_attention": LATENT + r"cache_attention/.*dot_general",
        "moe": MLP + r"moe/experts/ragged_dot",
    },
    # the same model with a latent of 128 values: every layer calls its
    # kernel's one jitted body under cache_attention (on the chip the custom
    # call's path ends `.../cache_attention/jit(_latent_decode_device)/
    # latent_decode_kernel/pallas_call`, which the kernels' rooflines read)
    "latent_step_kernel": {
        "cache_attention": LATENT + r"cache_attention/jit\(_latent_decode_device\)$",
        "kernel_scope": r"^latent_decode_kernel/",
        "kv_scatter": LATENT + r"kv_scatter/scatter",
    },
    # a chunk with the kernel runs the layer as written: the kernel's call is
    # ALL the scope holds (`latent_chunk_roofline` divides by its time, and
    # tells a whole run of the program by one operation a layer there)
    "latent_prefill_chunk_kernel": {
        "cache_attention": LATENT + r"cache_attention/jit\(_latent_chunk_device\)$",
        "kernel_scope": r"^latent_chunk_kernel/",
        "kernel_call": r"^latent_chunk_kernel/latent_chunk_attention/pallas_call$",
        "kv_scatter": LATENT + r"kv_scatter/scatter",
    },
    # a latent model with four residual streams and a router bias: both
    # halves of every map under its Flax name (`hc_attn` / `hc_mlp` a block,
    # `hc_out` at the end), the product and the normalisations inside
    # `hc_pre`; the mixer's and the MLP's scopes where they were
    "streams_step": {
        "hc_pre": HC + r"hc_pre/",
        "hc_mix": HC + r"hc_pre/hc_mix/.*dot_general",
        "hc_sinkhorn": HC + r"hc_pre/hc_sinkhorn/while/body/",
        "hc_post": r"TransformerLM\)*/layers_\d+/hc_post/",
        "hc_out": r"TransformerLM\)*/hc_out\.pre/hc_pre/hc_mix/.*dot_general",
        "cache_attention": LATENT + r"cache_attention/.*dot_general",
        "moe": MLP + r"moe/router/logistic",
    },
    "streams_prefill_chunk": {
        "hc_mix": HC + r"hc_pre/hc_mix/.*dot_general",
        "hc_sinkhorn": HC + r"hc_pre/hc_sinkhorn/while/body/",
        "hc_post": r"TransformerLM\)*/layers_\d+/hc_post/",
        "hc_out": r"TransformerLM\)*/hc_out\.pre/hc_pre/",
        "absorb_q": LATENT + r"absorb_q/.*dot_general",
    },
    # a model that mixes gated short-convolution layers with attention at
    # head size 64 over sparse MLPs with a router bias and a tied head: the
    # mixer's scope is its Flax name, with the two products under theirs and
    # the tail's read, the taps and the tail's write under `conv_step` (one
    # token a row) or `conv_chunk` (a prefill chunk)
    "conv_step": {
        "gated_conv": CONV,
        "in_proj": CONV + r"in_proj/dot_general",
        "conv_step": CONV + r"conv_step/mul",
        "tail_read": CONV + r"conv_step/jit\(_take\)",
        "tail_write": CONV + r"conv_step/scatter",
        "out_proj": CONV + r"out_proj/dot_general",
        "cache_attention": LAYER + r"cache_attention/jit\(_per_device\)$",
        "kv_scatter": LAYER + r"kv_scatter/scatter",
        "moe": MLP + r"moe/router/logistic",
        "lm_head": r"TransformerLM\)*/lm_head/tok_embed\.attend/dot_general",
        "sample": r"^jit\(step\)/sample/",
    },
    "conv_prefill_chunk": {
        "gated_conv": CONV,
        "in_proj": CONV + r"in_proj/dot_general",
        "conv_chunk": CONV + r"conv_chunk/mul",
        "tail_write": CONV + r"conv_chunk/scatter",
        "out_proj": CONV + r"out_proj/dot_general",
        "cache_attention": LAYER + r"cache_attention/jit\(_chunk_per_device\)$",
        "kv_scatter": LAYER + r"kv_scatter/scatter",
        "moe": MLP + r"moe/experts/ragged_dot",
    },
    # a model that mixes linear layers whose decay is a vector a head (KDA:
    # low-rank gates under `kda_gate`) with NoPE attention under an
    # elementwise gate, every MLP sparse with a shared expert: the linear
    # kind's scopes where the hybrid model has them, `kda_gate` beside them
    # (the running sum of a chunk is the scan's own: no scope inside it)
    "kda_step": {
        "linear_attn": LINEAR + r"(q|k|v|o|b)_proj/dot_general",
        "kda_gate": LINEAR + r"kda_gate/(f|g)_proj_(a|b)/dot_general",
        "softplus": LINEAR + r"kda_gate/jit\(softplus\)",
        "short_conv": LINEAR + r"short_conv/",
        "recurrence": LINEAR + r"recurrence/",
        "state_write": LINEAR + r"recurrence/scatter",
        "gated_norm": LINEAR + r"gated_norm/",
        "attn_gate": LAYER + r"attn_gate/out_gate/dot_general",
        "cache_attention": LAYER + r"cache_attention/.*dot_general",
        "moe": MLP + r"moe/router/logistic",
        "shared_expert": MLP + r"moe/shared_expert/(gate|up|down)_proj/dot_general",
        "sample": r"^jit\(step\)/sample/",
    },
    "kda_step_kernel": {
        "recurrence": LINEAR + r"recurrence/jit\(_call\)$",
        "kernel_body": r"^paged_delta_step/",
        "tail_write": LINEAR + r"recurrence/scatter",
    },
    "kda_prefill_chunk": {
        "kda_gate": LINEAR + r"kda_gate/(f|g)_proj_(a|b)/dot_general",
        "running_sum": LINEAR + r"chunk_scan/jit\(cumsum\)",
        "short_conv": LINEAR + r"short_conv/",
        "chunk_scan": LINEAR + r"chunk_scan/.*dot_general",
        "state_write": LINEAR + r"chunk_scan/scatter",
        "gated_norm": LINEAR + r"gated_norm/",
        "attn_gate": LAYER + r"attn_gate/",
        "kv_scatter": LAYER + r"kv_scatter/scatter",
        "moe": MLP + r"moe/experts/ragged_dot",
    },
    # the kernels' calls carry their names (`name=` on the `pallas_call`), so
    # the trace says which form of the backward a step ran: in the resident
    # regime ONE call a layer (the backward's path goes through `checkpoint`)
    "ddp": {
        "rope": LAYER + r"rope/",
        "flash_attention": LAYER + r"flash_attention/",
        "flash_fwd": LAYER + r"flash_attention/flash_fwd/pallas_call$",
        "flash_bwd": r"/layers_\d+/attn/(attn\.\w+/)*flash_attention/flash_bwd/pallas_call$",
        "loss": r"(^|/)jvp\(loss\)/",
        "grad_reduce": r"(^|/)grad_reduce/(reduce_scatter|all_gather)",
        "optimizer": r"(^|/)optimizer/",
    },
    "fsdp": {
        "rope": LAYER + r"rope/",
        "dense_attention": LAYER + r"dense_attention/",
        "loss": r"^jit\(step\)/jvp\(loss\)/",
        "optimizer": r"^jit\(step\)/optimizer/",
    },
    "zero2_hook": {"grad_reduce": r"(^|/)grad_reduce/psum", "loss": r"(^|/)loss/psum"},
}


def _paths(lowered) -> dict:
    """The program's name as the trace's modules line has it, and the scope
    path of every operation (inside a shard_map body a path starts below
    the `jit(...)` segment; file names and argument names are left out)."""
    text = lowered.as_text(debug_info=True)
    return {
        "program": re.search(r"module @(\w+)", text).group(1),
        "paths": {p for p in re.findall(r'loc\("([^" ]+)"', text)
                  if "/" in p and not p.startswith("/")},
    }


def _model(d_model=32, **kw):
    cfg = TransformerConfig(
        vocab_size=64, d_model=d_model, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, max_seq_len=64, **kw)
    model = TransformerLM(cfg)
    return model, model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _pattern_model(head_size):
    from pytorch_distributed_example_tpu.models.transformer import LayerSpec, RopeSpec

    full = RopeSpec(5e5, 0.5, (8.0, 16, 64.0, 1.0, 1.4))
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=3, d_ff=64,
        max_seq_len=64, head_size=head_size, window=8, attn_gate=True,
        rope_pairs="halves", sparse_experts=4, sparse_top_k=2, sparse_d_ff=16,
        shared_d_ff=16, routed_scale=2.5, use_flash=False,
        layers=(LayerSpec("full", 4, full, "dense"),
                LayerSpec("window", 8, RopeSpec(1e4), "sparse"),
                LayerSpec("full", 4, full, "sparse")))
    model = TransformerLM(cfg)
    return model, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _hybrid_model(key_dim=8, value_dim=12):
    from pytorch_distributed_example_tpu.models.transformer import LayerSpec, RopeSpec

    full = LayerSpec("full", rope=RopeSpec(rotary_fraction=0.0))
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=4, d_ff=64, max_seq_len=64,
        use_flash=False, post_norm=True, qk_norm=True, linear_heads=2,
        linear_key_dim=key_dim, linear_value_dim=value_dim, linear_neg_eigval=True,
        layers=(LayerSpec("linear"),) * 3 + (full,))
    model = TransformerLM(cfg)
    return model, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _kda_model(width=8):
    """A softmax layer (NoPE, an elementwise gate) and two linear ones with a
    decay a key channel through rank-4 pairs, sparse MLPs with a shared
    expert and half the experts held."""
    from pytorch_distributed_example_tpu.models.transformer import LayerSpec, RopeSpec

    nope = RopeSpec(rotary_fraction=0.0)
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=3, d_ff=64, max_seq_len=64,
        use_flash=False, rope_pairs="halves", attn_out_gate=True, linear_heads=2,
        linear_key_dim=width, linear_value_dim=width, linear_neg_eigval=True,
        linear_decay="channel", linear_gate_rank=4, sparse_score="sigmoid",
        sparse_choice_bias=True, sparse_experts=4, sparse_top_k=2, sparse_d_ff=16,
        shared_d_ff=16, experts_held=(0, 2),
        layers=(LayerSpec("full", rope=nope, mlp="sparse"),
                LayerSpec("linear", rope=nope, mlp="sparse"),
                LayerSpec("linear", rope=nope, mlp="sparse")))
    model = TransformerLM(cfg)
    return model, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _latent_model(rank=16, **kw):
    from pytorch_distributed_example_tpu.models.transformer import LayerSpec

    kw = kw or {"sandwich_norm": True}
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq_len=64,
        use_flash=False, sparse_score="sigmoid", sparse_experts=4,
        sparse_top_k=2, sparse_d_ff=16, shared_d_ff=16, routed_scale=2.5,
        latent_q_rank=8, latent_kv_rank=rank, latent_nope_dim=8, latent_rope_dim=4,
        latent_v_dim=8, layers=(LayerSpec("latent"), LayerSpec("latent", mlp="sparse")),
        **kw)
    model = TransformerLM(cfg)
    return model, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _conv_model():
    """Conv and attention layers 2:1 at head size 64 (4 heads over 2 KV
    heads of a 256-wide model: the paged pool holds the two as one
    128-lane row and both kernels take it), a dense then sparse MLPs with
    a router bias, a tied head."""
    from pytorch_distributed_example_tpu.models.transformer import LayerSpec, RopeSpec

    rope = RopeSpec(1e6)
    cfg = TransformerConfig(
        vocab_size=64, d_model=256, n_heads=4, n_kv_heads=2, n_layers=3, d_ff=64,
        max_seq_len=64, use_flash=False, rope_pairs="halves", qk_head_norm=True,
        tie_embeddings=True, sparse_score="sigmoid", sparse_choice_bias=True,
        sparse_norm_eps=1e-6, sparse_experts=4, sparse_top_k=2, sparse_d_ff=16,
        experts_held=(0, 2),
        layers=(LayerSpec("conv", rope=rope), LayerSpec("conv", rope=rope, mlp="sparse"),
                LayerSpec("full", rope=rope, mlp="sparse")))
    model = TransformerLM(cfg)
    return model, jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _loss(logits, y):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], y[:, 1:]).mean()


@pytest.fixture(scope="module")
def serve_paths():
    from pytorch_distributed_example_tpu.serve.cache import init_paged_cache
    from pytorch_distributed_example_tpu.serve.decode import paged_programs

    model, variables = _model()
    params = variables["params"]
    prefill_chunk, first_token, _, step = paged_programs(model, 0.0, None)
    S, bs, nblk = 2, 8, 16
    tree = init_paged_cache(model, nblk, bs)
    bt = jnp.zeros((S, 64 // bs), jnp.int32)
    lanes = jnp.zeros((S,), jnp.int32)
    rngs = jnp.zeros((S, 2), jnp.uint32)
    wide, wide_vars = _model(d_model=512)  # 4 heads of 128
    wide_step = paged_programs(wide, 0.0, None)[3]
    out = {}
    for name, head in (("pattern_step", 16), ("pattern_step_kernel", 128)):
        pattern, pvars = _pattern_model(head)
        pchunk, _, _, pstep = paged_programs(pattern, 0.0, None)
        ptree = init_paged_cache(pattern, nblk, bs, window_blocks=8)
        pair = (bt, bt)  # the full layers' tables and the window layers'
        out[name] = _paths(pstep.lower(
            pvars["params"], ptree, lanes, lanes, rngs, pair))
        if head == 16:
            out["pattern_prefill_chunk"] = _paths(pchunk.lower(
                pvars["params"], ptree, jnp.zeros((1, 16), jnp.int32),
                (bt[:1], bt[:1]), 0))
    hybrid, hvars = _hybrid_model()
    hchunk, _, _, hstep = paged_programs(hybrid, 0.0, None)
    htree = init_paged_cache(hybrid, nblk, bs, state_blocks=S)
    state = jnp.zeros((S, 1), jnp.int32)  # each row's state block
    out["hybrid_step"] = _paths(hstep.lower(
        hvars["params"], htree, lanes, lanes, rngs, (bt, state)))
    out["hybrid_prefill_chunk"] = _paths(hchunk.lower(
        hvars["params"], htree, jnp.zeros((1, 16), jnp.int32), (bt[:1], state[:1]), 0))
    for suffix, rank in (("", 16), ("_kernel", 128)):
        latent, lvars = _latent_model(rank)
        lchunk, _, _, lstep = paged_programs(latent, 0.0, None)
        ltree = init_paged_cache(latent, nblk, bs)
        out["latent_step" + suffix] = _paths(lstep.lower(
            lvars["params"], ltree, lanes, lanes, rngs, bt))
        out["latent_prefill_chunk" + suffix] = _paths(lchunk.lower(
            lvars["params"], ltree, jnp.zeros((1, 16), jnp.int32), bt[:1], 0))
    streams, svars = _latent_model(hc_mult=4, sparse_choice_bias=True)
    schunk, _, _, sstep = paged_programs(streams, 0.0, None)
    stree = init_paged_cache(streams, nblk, bs)
    out["streams_step"] = _paths(sstep.lower(svars["params"], stree, lanes, lanes, rngs, bt))
    out["streams_prefill_chunk"] = _paths(schunk.lower(
        svars["params"], stree, jnp.zeros((1, 16), jnp.int32), bt[:1], 0))
    conv, cvars = _conv_model()
    cchunk, _, _, cstep = paged_programs(conv, 0.0, None)
    ctree = init_paged_cache(conv, nblk, bs, state_blocks=S)
    out["conv_step"] = _paths(cstep.lower(
        cvars["params"], ctree, lanes, lanes, rngs, (bt, state)))
    out["conv_prefill_chunk"] = _paths(cchunk.lower(
        cvars["params"], ctree, jnp.zeros((1, 16), jnp.int32), (bt[:1], state[:1]), 0))
    for name, width in (("kda_step", 8), ("kda_step_kernel", 64)):
        kda, kvars = _kda_model(width)
        kchunk, _, _, kstep = paged_programs(kda, 0.0, None)
        ktree = init_paged_cache(kda, nblk, bs, state_blocks=S)
        out[name] = _paths(kstep.lower(
            kvars["params"], ktree, lanes, lanes, rngs, (bt, state)))
        if width == 8:
            out["kda_prefill_chunk"] = _paths(kchunk.lower(
                kvars["params"], ktree, jnp.zeros((1, 16), jnp.int32),
                (bt[:1], state[:1]), 0))
    wide_hybrid, wvars = _hybrid_model(16, 64)
    out["hybrid_step_kernel"] = _paths(paged_programs(wide_hybrid, 0.0, None)[3].lower(
        wvars["params"], init_paged_cache(wide_hybrid, nblk, bs, state_blocks=S),
        lanes, lanes, rngs, (bt, state)))
    return {
        **out,
        "step": _paths(step.lower(params, tree, lanes, lanes, rngs, bt)),
        "step_kernel": _paths(wide_step.lower(
            wide_vars["params"], init_paged_cache(wide, nblk, bs),
            lanes, lanes, rngs, bt)),
        "prefill_chunk": _paths(prefill_chunk.lower(
            params, tree, jnp.zeros((1, 16), jnp.int32), bt[:1], 0)),
        "first_token": _paths(first_token.lower(
            jnp.zeros((16, 64), jnp.float32), 3, 7)),
    }


@pytest.fixture(scope="module")
def train_paths(world):
    W = world.size()
    x = jnp.zeros((W, 64), jnp.int32)
    out = {}

    # flash on: the kernel is interpreted on the CPU, the scope is the same
    model, variables = _model(use_flash=True, remat=True)
    ddp = tdx.DistributedDataParallel(model, variables)
    step = ddp.make_train_step(optax.adamw(1e-3), _loss)
    p, o = ddp.params, step.init_opt_state(ddp.params)
    p, o, _ = step(p, o, x, x)  # under ZeRO the program is built at first dispatch
    out["ddp"] = _paths(step._jitted.lower(p, o, {}, x, x, jax.random.PRNGKey(0)))

    from pytorch_distributed_example_tpu.mesh import init_device_mesh
    from pytorch_distributed_example_tpu.models import transformer_sharding_rules
    from pytorch_distributed_example_tpu.parallel import fully_shard
    from pytorch_distributed_example_tpu.parallel.fsdp import make_zero2_train_step
    from pytorch_distributed_example_tpu.parallel import comm_hooks

    model, variables = _model(use_flash=False, remat=True)
    mesh = init_device_mesh(("fsdp", "tp"), (4, 1), devices=jax.devices()[:4])
    rules = transformer_sharding_rules("tp", "fsdp")
    mod = fully_shard(model, variables, mesh, axis="fsdp", rules=rules,
                      data_axes=("fsdp",))
    fstep = mod.make_train_step(optax.adamw(1e-3), _loss)
    out["fsdp"] = _paths(fstep.lower(
        mod.params, fstep.init_opt_state(mod.params), x[:4], x[:4]))

    zstep = make_zero2_train_step(
        lambda p, xb: model.apply(p, xb), _loss, optax.adamw(1e-3), mesh,
        axis="fsdp", data_axes=("fsdp",), comm_hook=comm_hooks.allreduce_hook)
    # with a hook the step is a host wrapper around the jitted one
    out["zero2_hook"] = _paths(jax.jit(zstep).lower(
        variables, zstep.init_opt_state(variables), x[:4], x[:4]))
    return out


SERVE = ("step", "step_kernel", "prefill_chunk", "first_token", "pattern_step",
         "pattern_prefill_chunk", "pattern_step_kernel", "hybrid_step",
         "hybrid_step_kernel", "hybrid_prefill_chunk", "latent_step",
         "latent_prefill_chunk", "latent_step_kernel", "latent_prefill_chunk_kernel",
         "streams_step", "streams_prefill_chunk", "conv_step", "conv_prefill_chunk",
         "kda_step", "kda_step_kernel", "kda_prefill_chunk")
CASES = [(prog, scope) for prog, scopes_ in EXPECTED.items() for scope in scopes_]


@pytest.mark.parametrize("program,scope", CASES, ids=[f"{p}-{s}" for p, s in CASES])
def test_scope_sits_under_its_flax_path(program, scope, request):
    serve = program in SERVE
    paths = request.getfixturevalue(
        "serve_paths" if serve else "train_paths")[program]["paths"]
    rx = re.compile(EXPECTED[program][scope])
    assert any(rx.search(p) for p in paths), (
        f"no operation of {program} is traced under {rx.pattern}; paths with "
        f"{scope!r}: {sorted(p for p in paths if scope in p)[:5]}")


def test_the_decode_step_scans_nothing_and_a_chunk_runs_no_recurrence(serve_paths):
    """The two cached forms of a linear layer are told apart by the call's
    shape, and each program holds one of them only."""
    step, chunk = serve_paths["hybrid_step"], serve_paths["hybrid_prefill_chunk"]
    assert step["program"] == "jit_step" and chunk["program"] == "jit_prefill_chunk"
    assert not [p for p in step["paths"] if "chunk_scan" in p]
    assert not [p for p in chunk["paths"] if "/recurrence/" in p]
    # a layer that is not linear traces nothing under the mixer's scopes
    assert not [p for p in step["paths"] if "layers_3/linear_attn" in p]
    assert not [p for p in step["paths"] if re.search(r"layers_[012]/attn/", p)]
    # with the kernel in the step the state is read and written by it alone:
    # one gather (the conv tail's) a layer where the plain update has two
    kernel = serve_paths["hybrid_step_kernel"]["paths"]
    calls = [p for p in kernel if p.endswith("recurrence/jit(_call)")]
    assert len(calls) == 3  # one a linear layer, each under its own layer's scope
    assert not [p for p in step["paths"] if "paged_delta_step" in p]


def test_the_train_step_runs_one_backward_kernel_a_layer(train_paths):
    """Both flash kernels sit under every layer's `flash_attention` scope,
    and nothing of the streamed regime's two-kernel backward is traced."""
    paths = train_paths["ddp"]["paths"]
    for kernel in ("flash_fwd", "flash_bwd"):
        calls = {re.search(r"layers_\d+", p).group() for p in paths
                 if p.endswith(f"flash_attention/{kernel}/pallas_call")}
        assert calls == {"layers_0", "layers_1"}, (kernel, calls)
    assert not [p for p in paths if "flash_bwd_dkdv" in p or "flash_bwd_dq" in p]


def test_a_model_of_one_stream_traces_no_map(serve_paths):
    """The streams' scopes exist where `hc_mult` is above 1 and nowhere else;
    there every block has both maps and the model ends in `hc_out`."""
    for program in ("latent_step", "latent_prefill_chunk", "step", "pattern_step"):
        assert not [p for p in serve_paths[program]["paths"] if "/hc_" in p], program
    paths = serve_paths["streams_step"]["paths"]
    maps = {m.group(1) for p in paths if (m := re.search(r"(layers_\d+/hc_\w+)\.pre/hc_pre", p))}
    assert maps == {f"layers_{i}/hc_{s}" for i in (0, 1) for s in ("attn", "mlp")}
    assert [p for p in paths if "hc_out.pre/hc_pre" in p]
    assert not [p for p in paths if "hc_out" in p and "hc_sinkhorn" in p]  # the end mixes, no more


def test_a_chunk_with_the_latent_kernel_moves_no_up_projection_outside_it(serve_paths):
    """The chunk program whose layers call `ops.latent_chunk_attention` holds
    no `absorb_q` / `absorb_out` product and no `kv_up` one: a key's heads
    are made inside the kernel's call, under `latent_chunk_kernel`, and
    nowhere else. The step beside it, the gather fallback and a model of
    several streams keep the absorbed products (`EXPECTED` holds theirs)."""
    import functools

    from pytorch_distributed_example_tpu.ops import paged_attention

    chunk = serve_paths["latent_prefill_chunk_kernel"]
    assert chunk["program"] == "jit_prefill_chunk"
    for scope in ("absorb_q", "absorb_out", "kv_up", "kv_gather"):
        assert not [p for p in chunk["paths"] if f"/{scope}" in p], scope
    calls = [p for p in chunk["paths"] if p.endswith("cache_attention/jit(_latent_chunk_device)")]
    assert sorted(re.search(r"layers_\d+", p).group() for p in calls) == ["layers_0", "layers_1"]
    step = serve_paths["latent_step_kernel"]["paths"]
    assert [p for p in step if "/absorb_q/" in p] and [p for p in step if "/absorb_out/" in p]
    # under the kernel's scope: the call alone
    sd = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(functools.partial(
        paged_attention._latent_chunk_device.__wrapped__, scale=0.1, interpret=False))(
        sd((1, 16, 4, 8), jnp.float32), sd((1, 16, 4, 4), jnp.float32),
        sd((128, 4, 8), jnp.float32), sd((128, 4, 8), jnp.float32),
        sd((16, 8, 256), jnp.float32), sd((1, 8), jnp.int32), sd((1,), jnp.int32)).jaxpr
    under = [e.primitive.name for e in jaxpr.eqns
             if "latent_chunk_kernel" in str(e.source_info.name_stack)]
    assert under == ["pallas_call"]
    assert "dot_general" not in [e.primitive.name for e in jaxpr.eqns]


def test_a_latent_step_holds_one_kernel_call_a_layer_and_its_work_list_outside(serve_paths):
    """What `bench_matrix/readers/latent_steps.kernel_seconds(calls=)` holds
    on to: every latent layer calls `jit(_latent_decode_device)` under its own
    `cache_attention`, and of that call's operations exactly ONE, the
    `pallas_call`, is traced under `latent_decode_kernel`; the work list
    (with its shared half: the pairwise runs, the cumulative sums) is plain
    XLA outside the kernel's scope, where the layers' copies merge."""
    import functools

    from pytorch_distributed_example_tpu.ops import paged_attention

    paths = serve_paths["latent_step_kernel"]["paths"]
    calls = [p for p in paths if p.endswith("cache_attention/jit(_latent_decode_device)")]
    assert sorted(re.search(r"layers_\d+", p).group() for p in calls) == ["layers_0", "layers_1"]
    # on the CPU the interpreted kernel's operations all sit below the call's name
    scoped = [p for p in paths if p.startswith("latent_decode_kernel/")]
    assert scoped and all(
        p.startswith("latent_decode_kernel/latent_decode_attention/") for p in scoped)
    assert [p for p in scoped if p.endswith("/pallas_call")] == [
        "latent_decode_kernel/latent_decode_attention/pallas_call"]
    sd = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(functools.partial(
        paged_attention._latent_decode_device.__wrapped__, scale=0.1, rank=128,
        interpret=False))(
        sd((2, 4, 256), jnp.float32), sd((16, 8, 256), jnp.float32),
        sd((2, 8), jnp.int32), sd((2,), jnp.int32)).jaxpr
    under = [e.primitive.name for e in jaxpr.eqns
             if "latent_decode_kernel" in str(e.source_info.name_stack)]
    assert under == ["pallas_call"]
    assert len(jaxpr.eqns) > 40  # the work list, with its shared half


def test_a_conv_layer_traces_one_form_a_program_and_no_attention(serve_paths):
    """The step holds `conv_step` and no `conv_chunk`, a chunk the reverse;
    a conv layer traces nothing under `attn`, an attention layer nothing
    under `gated_conv`; at head size 64 the step gathers no keys."""
    step, chunk = serve_paths["conv_step"], serve_paths["conv_prefill_chunk"]
    assert step["program"] == "jit_step" and chunk["program"] == "jit_prefill_chunk"
    assert not [p for p in step["paths"] if "conv_chunk" in p]
    assert not [p for p in chunk["paths"] if "/conv_step/" in p]
    for low in (step, chunk):
        assert not [p for p in low["paths"] if re.search(r"layers_[01]/attn/", p)]
        assert not [p for p in low["paths"] if "layers_2/gated_conv" in p]
        assert not [p for p in low["paths"] if "kv_gather" in p]
    mixers = {re.search(r"layers_\d+", p).group() for p in step["paths"]
              if "/gated_conv/conv_step/" in p}
    assert mixers == {"layers_0", "layers_1"}


def test_the_kernel_step_gathers_nothing(serve_paths):
    """With the decode kernel in the step no operation is traced under
    `kv_gather`, and the old path's step keeps both scopes."""
    assert serve_paths["step_kernel"]["program"] == "jit_step"
    assert not [p for p in serve_paths["step_kernel"]["paths"] if "kv_gather" in p]
    calls = [p for p in serve_paths["step_kernel"]["paths"]
             if p.endswith("cache_attention/jit(_per_device)")]
    assert len(calls) == 2  # one a layer, each under its own layer's scope
    assert not [p for p in serve_paths["step"]["paths"]
                if "paged_decode_attention" in p]
    assert not [p for p in serve_paths["prefill_chunk"]["paths"]
                if "paged_decode_attention" in p]


# the metric files' own patterns, against the same lowerings
METRICS = Path(__file__).resolve().parents[1] / "bench_matrix" / "layer_metrics"
READ_BY = {
    "decode_cache_attention_ms": ["step", "step_kernel", "pattern_step", "hybrid_step",
                                  "latent_step", "latent_step_kernel", "conv_step"],
    "decode_latent_attention_ms": ["latent_step", "latent_step_kernel"],
    "prefill_latent_attention_ms": ["latent_prefill_chunk", "latent_prefill_chunk_kernel"],
    "prefill_latent_cache_attention_ms": ["latent_prefill_chunk",
                                          "latent_prefill_chunk_kernel"],
    "latent_decode_roofline": ["latent_step_kernel"],
    "latent_chunk_roofline": ["latent_prefill_chunk_kernel"],
    "decode_hc_ms": ["streams_step"],
    "decode_hc_sinkhorn_ms": ["streams_step"],
    "prefill_hc_ms": ["streams_prefill_chunk"],
    "hc_chunk_roofline": ["streams_prefill_chunk"],
    "decode_linear_attention_ms": ["hybrid_step", "hybrid_step_kernel", "kda_step",
                                   "kda_step_kernel"],
    "decode_recurrence_ms": ["hybrid_step", "hybrid_step_kernel", "kda_step",
                             "kda_step_kernel"],
    "recurrence_decode_roofline": ["hybrid_step", "hybrid_step_kernel", "kda_step",
                                   "kda_step_kernel"],
    "prefill_linear_attention_ms": ["hybrid_prefill_chunk", "kda_prefill_chunk"],
    "prefill_chunk_scan_ms": ["hybrid_prefill_chunk", "kda_prefill_chunk"],
    "prefill_kda_gate_ms": ["kda_prefill_chunk"],
    "kda_decode_linear_attention_ms": ["kda_step", "kda_step_kernel"],
    "kda_decode_recurrence_ms": ["kda_step", "kda_step_kernel"],
    "kda_recurrence_decode_roofline": ["kda_step", "kda_step_kernel"],
    "kda_prefill_linear_attention_ms": ["kda_prefill_chunk"],
    "kda_prefill_chunk_scan_ms": ["kda_prefill_chunk"],
    "chunk_scan_roofline": ["kda_prefill_chunk"],
    "decode_gated_conv_ms": ["conv_step"],
    "prefill_gated_conv_ms": ["conv_prefill_chunk"],
    "gqa64_decode_roofline": ["conv_step"],
    "decode_moe_ms": ["pattern_step", "latent_step", "conv_step", "kda_step"],
    "prefill_moe_ms": ["pattern_prefill_chunk", "latent_prefill_chunk", "conv_prefill_chunk",
                       "kda_prefill_chunk"],
    "decode_window_attention_ms": ["pattern_step", "pattern_step_kernel"],
    "moe_decode_roofline": ["pattern_step", "conv_step", "kda_step"],
    "prefill_cache_attention_ms": ["prefill_chunk"],
    "train_mlp_ms": ["ddp", "fsdp"],
    "train_attention_ms": ["ddp", "fsdp"],
    "train_head_loss_ms": ["ddp", "fsdp"],
    "train_optimizer_ms": ["ddp", "fsdp"],
}


@pytest.mark.parametrize("metric", sorted(READ_BY))
def test_metric_file_finds_operations_in_the_program_it_names(metric, request):
    from bench_matrix.reduce import scopes

    args = json.loads((METRICS / f"{metric}.json").read_text())["args"]
    for program in READ_BY[metric]:
        serve = program in SERVE
        low = request.getfixturevalue("serve_paths" if serve else "train_paths")[program]
        assert re.search(args["program"], low["program"]), (metric, low["program"])
        rx = re.compile(args["scope"])
        assert any(rx.search("/".join(scopes.names(p)[0])) for p in low["paths"]), (
            metric, program)


_RENAMED = """
import os, sys, tempfile
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
import jax, jax.numpy as jnp
from pytorch_distributed_example_tpu import _compat
_compat.enable_compile_cache(tempfile.mkdtemp(), min_compile_secs=0.0)

def make(scope):
    def f(x):
        with jax.named_scope(scope):
            return jnp.tanh(x @ x) * 3
    return jax.jit(f)

x = jnp.ones((64, 64))
make("old_name")(x).block_until_ready()  # compiled and written to the cache
jax.clear_caches()
text = make("new_name").lower(x).compile().as_text()
print("old_name" in text, "new_name" in text)
"""


def test_a_renamed_scope_is_not_served_the_cached_program_with_the_old_name():
    """JAX's default cache key strips scope paths: the second program would
    load the first's executable and a trace would show `old_name` (seen on
    the chip between two checkouts). `enable_compile_cache` keys on them."""
    import os
    import subprocess
    import sys

    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c", _RENAMED], cwd=root, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-2:] == ["False", "True"]
