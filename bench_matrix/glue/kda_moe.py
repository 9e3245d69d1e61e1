"""Glue for a decoder that mixes linear-attention layers whose decay is a
vector a head (Kimi Delta Attention: low-rank gates, a sigmoid output gate)
with NoPE grouped-query attention under an elementwise output gate, every
layer's MLP a sigmoid-routed dropless MoE with a correction bias and a
shared expert, of which this chip holds a share of the experts and a slice
of the vocabulary, as the program's `TransformerLM` builds it from
`TransformerConfig.layers` with "linear" layers at `linear_decay="channel"`:
the configuration's Hugging Face keys on one side, the program's constructor
keywords and parameter names on the other. Pairs with
`reference/kda_moe.py`, whose layer dict it fills. The operation and byte
counts of this kind's roofline metrics (`recurrence_decode_call`,
`moe_decode_call`, `chunk_scan_call`) live here too, beside the shapes they
are counted from."""

from __future__ import annotations

import jax

from .. import spec
from ..modelglue import DTYPES


def routed_experts(config: dict) -> int:
    """The router's width: the published count, whatever share is held."""
    return config.get("published", config)["n_routed_experts"]


def is_linear(config: dict, i: int) -> bool:
    """Layer i is a linear one unless `gqa_layers` lists it. The list stays
    the published one under a cut in depth (`check_cut` lets only a list of
    one entry a layer change): its indices at or past `num_hidden_layers`
    say nothing."""
    return i not in config["gqa_layers"]


def linear_sizes(config: dict) -> tuple:
    """(heads, key and value width, conv taps) of a linear layer."""
    lin = config["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def gate_rank(config: dict) -> int:
    """`assumed` (2): `kda_use_full_proj: false` is a low-rank pair for the
    decay and for the output gate, of rank = the head size (`build_model`
    refuses true: full projections are not carried)."""
    return config["linear_attn_config"]["head_dim"]


def build_model(config: dict, max_seq_len: int, remat: bool):
    """The program's model object at the configuration's sizes."""
    from pytorch_distributed_example_tpu.models.transformer import (
        LayerSpec, RopeSpec, TransformerConfig, TransformerLM,
    )

    lacks = {"linear_decay", "linear_gate_rank", "attn_out_gate"} - set(
        TransformerConfig.__dataclass_fields__)
    if lacks:  # a program from before the vector decay: refused here, at once
        raise spec.SpecError(f"this program's TransformerConfig has no {sorted(lacks)}")
    lin = config["linear_attn_config"]
    refused = {
        "norm_topk_prob: false": not config["norm_topk_prob"],
        "first_k_dense_replace above 0": config["first_k_dense_replace"],
        "tie_word_embeddings": config["tie_word_embeddings"],
        "kda_use_full_proj: true": config["kda_use_full_proj"],
        "linear key and value head counts that differ":
            lin["num_kv_heads"] not in (None, lin["num_heads"]),
    }
    for what, said in refused.items():
        if said:
            raise spec.SpecError(f"{what} is not carried")
    n = config["num_hidden_layers"]
    # `use_rope: false`: a rope that rotates nothing (NoPE)
    rope = (RopeSpec(float(config["rope_theta"]),
                     rotary_fraction=float(config["partial_rotary_factor"]))
            if config["use_rope"] else RopeSpec(rotary_fraction=0.0))
    held = config["n_routed_experts"]
    heads, width, taps = linear_sizes(config)
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"], n_layers=n,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_size=config["head_dim"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=config["rms_norm_eps"],
        layers=tuple(
            LayerSpec("linear" if is_linear(config, i) else "full", rope=rope, mlp="sparse")
            for i in range(n)
        ),
        rope_pairs="halves", attn_out_gate=bool(config["use_gqa_gate"]),
        linear_heads=heads, linear_key_dim=width, linear_value_dim=width,
        linear_conv=taps, linear_neg_eigval=bool(config["kda_allow_neg_eigval"]),
        linear_decay="channel", linear_gate_rank=gate_rank(config),
        # `assumed` (5), (6): the family's router; the router keeps its
        # published width, the chip holds the leading `n_routed_experts`
        sparse_score="sigmoid", sparse_choice_bias=True,
        sparse_experts=routed_experts(config),
        experts_held=None if held == routed_experts(config) else (0, held),
        sparse_top_k=config["num_experts_per_tok"],
        sparse_d_ff=config["moe_intermediate_size"],
        shared_d_ff=config["n_shared_experts"] * config["moe_intermediate_size"],
        routed_scale=float(config["routed_scaling_factor"]),
        causal=True, use_flash=False, remat=remat,
        dtype=DTYPES[config["dtype"]["activations"]],
    )
    return TransformerLM(cfg)


# the reference's name for a layer's array -> where the program keeps it
NORMS = {"attn_norm": ("attn_norm", "scale"), "mlp_norm": ("mlp_norm", "scale")}
LINEAR = {
    "wq": ("linear_attn", "q_proj", "kernel"), "wk": ("linear_attn", "k_proj", "kernel"),
    "wv": ("linear_attn", "v_proj", "kernel"), "wo": ("linear_attn", "o_proj", "kernel"),
    "wb": ("linear_attn", "b_proj", "kernel"), "conv": ("linear_attn", "conv"),
    "w_f1": ("linear_attn", "f_proj_a", "kernel"), "w_f2": ("linear_attn", "f_proj_b", "kernel"),
    "w_g1": ("linear_attn", "g_proj_a", "kernel"), "w_g2": ("linear_attn", "g_proj_b", "kernel"),
    "A_log": ("linear_attn", "A_log"), "dt_bias": ("linear_attn", "dt_bias"),
    "norm": ("linear_attn", "norm"),
}
ATTENTION = {
    "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "w_out_gate": ("attn", "out_gate", "kernel"),
}
SPARSE = {
    "router": ("mlp", "router"), "router_bias": ("mlp", "router_bias"),
    "experts_gate": ("mlp", "experts_gate"), "experts_up": ("mlp", "experts_up"),
    "experts_down": ("mlp", "experts_down"),
    "shared_gate": ("mlp", "shared_expert", "gate_proj", "kernel"),
    "shared_up": ("mlp", "shared_expert", "up_proj", "kernel"),
    "shared_down": ("mlp", "shared_expert", "down_proj", "kernel"),
}


class Layers:
    """What `reference_parts` hands the reference as its layers: iterated,
    one layer's weights at a time in the reference's names; asked
    (`system_routing`), the experts the SYSTEM's sparse layers chose for a
    sequence."""

    def __init__(self, params, put):
        self.params, self.put = params, put
        self.count = sum(1 for k in params if k.startswith("layers_"))

    def __iter__(self):
        for i in range(self.count):
            blk = self.params[f"layers_{i}"]
            names = dict(NORMS, **(LINEAR if "linear_attn" in blk else ATTENTION), **SPARSE)
            yield {ours: self.put(_at(blk, path)) for ours, path in names.items()}

    def system_routing(self, tokens, config: dict) -> dict:
        """{layer: (len(tokens), top_k) int32}: the experts the program's
        model chose for each token when the sequence is prefilled the way
        the engine under test prefills it, -1 where the sequence was not
        replayed.

        Sigmoid top-8 of 320 by score plus bias flips on rounding wherever
        the eighth and ninth biased scores lie within bfloat16's accumulated
        rounding (`glue/sparse_window.py::Layers.system_routing` says why
        the reference asks). The engine hands out no routing, so the prompt
        is replayed here through the same model call the engine's
        `prefill_chunk` program makes (`serve/decode.py::paged_programs`):
        chunks of `prefill_chunk_tokens` into a paged cache of the engine's
        block and table shapes (a pool of one row, with its one state
        block), and then the LAST chunk as the engine cuts it: what is left
        of the prompt in the bucket that covers it, padded with token id -1,
        which the program tells from tokens. The check's prompt ends inside
        a bucket and the rows it compares lie in that chunk. The sequence's
        last `decoded_tail` tokens were decoded one at a time and keep -1."""
        import functools

        import jax.numpy as jnp
        import numpy as np

        from pytorch_distributed_example_tpu.serve.bucketing import (
            bucket_for, bucket_lengths,
        )
        from pytorch_distributed_example_tpu.serve.cache import PagedKVCache

        shape = config["model"]["check"]["replay"]
        chunk = shape["prefill_chunk_tokens"]
        model = build_model(config, shape["max_seq_len"], remat=False)
        buckets = bucket_lengths(shape["max_seq_len"], shape["min_bucket"])
        sparse = model.cfg.sparse_layers
        cache = PagedKVCache(model, 1, block_size=shape["block_size"], chunk_tokens=chunk)
        slot = cache.allocate()

        @functools.partial(jax.jit, donate_argnums=(1,))
        def chosen_in_chunk(params, tree, tokens, tables, start):
            _, out = model.apply(
                {"params": params, "cache": tree}, jnp.maximum(tokens, 0), decode=True,
                positions=jnp.asarray(start, jnp.int32)[None], block_tables=tables,
                mutable=["cache", "intermediates"], row_mask=tokens >= 0,
            )
            return out["cache"], [
                out["intermediates"][f"layers_{i}"]["mlp"]["moe_chosen"][0][0]
                for i in sparse
            ]

        tokens = np.asarray(tokens, np.int32)
        n_prompt = len(tokens) - shape["decoded_tail"]
        told = {i: np.full((len(tokens), model.cfg.sparse_top_k), -1, np.int32)
                for i in sparse}
        start = 0
        while start < n_prompt:
            # `ServeEngine._prefill_tick` with a budget of one chunk a step
            size = min(bucket_for(min(n_prompt - start, chunk), buckets), chunk)
            end = min(start + size, n_prompt)
            piece = np.full((1, size), -1, np.int32)
            piece[0, :end - start] = tokens[start:end]
            cache.ensure_blocks(slot, end - 1, start)
            cache.tree, chosen = chosen_in_chunk(
                self.params, cache.tree, jnp.asarray(piece),
                cache.tables(slice(slot, slot + 1)), start,
            )
            for i, c in zip(sparse, chosen):
                told[i][start:end] = np.asarray(c)[:end - start]
            start = end
        return told


def _at(node, path):
    for k in path:
        node = node[k]
    return node


def reference_parts(variables, device=None):
    """(embedding, the layers (`Layers`), final norm, output matrix) in the
    plain reference's own names, each layer moved to `device` only when
    asked for."""
    p = variables["params"] if "params" in variables else variables
    put = (lambda a: jax.device_put(a, device)) if device is not None else (lambda a: a)
    return (
        put(p["tok_embed"]["embedding"]), Layers(p, put),
        put(p["final_norm"]["scale"]), put(p["lm_head"]["kernel"]),
    )


# --- counts from shapes -----------------------------------------------------

def linear_layers(config: dict) -> int:
    return sum(is_linear(config, i) for i in range(config["num_hidden_layers"]))


def mixer_params(config: dict, i: int, matmuls_only: bool = False) -> int:
    """Layer i's mixer. Linear (KDA): q, k, v and o, the decay's and the
    output gate's projections (a low-rank pair each), beta's, and the small
    ones: conv taps, `A_log` a head, `dt_bias` a channel, the gated norm's
    scale. Softmax: q, k, v, o and the elementwise gate's projection."""
    d = config["hidden_size"]
    if is_linear(config, i):
        h, w, taps = linear_sizes(config)
        rank = gate_rank(config)
        gates = 2 * (d * rank + rank * h * w)
        small = taps * 3 * h * w + h + h * w + w
        return 4 * d * h * w + gates + d * h + (0 if matmuls_only else small)
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return 2 * d * q + 2 * d * kv + (d * q if config["use_gqa_gate"] else 0)


def expert_params(config: dict) -> int:
    """One expert, routed or shared: gate, up and down of a SwiGLU."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held_per_token(config: dict) -> float:
    """Of a token's `num_experts_per_tok` assignments, those that fall to
    the experts THIS chip holds, in expectation over a router that spreads
    its choices evenly: top_k x held / published (8 x 40 / 320 = 1)."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / routed_experts(config))


def layer_params(config: dict, i: int, active: bool = False) -> float:
    """Every parameter of layer i with its two norms: all it holds here
    (`n_routed_experts` experts, the shared expert, router, bias), or with
    `active` the matrix products a token is multiplied by ON THIS CHIP: the
    held experts' expected share of its assignments (`held_per_token`), the
    shared expert and the router. The count feeds `serve_mfu_pct`, a share
    of THIS chip's peak; the lesser count is the safe one."""
    d, e = config["hidden_size"], routed_experts(config)
    shared = config["n_shared_experts"] * expert_params(config)
    mixer = mixer_params(config, i, matmuls_only=active)
    if active:
        return mixer + held_per_token(config) * expert_params(config) + shared + d * e
    return (mixer + config["n_routed_experts"] * expert_params(config) + shared
            + d * e + e + 2 * d)


def param_count(config: dict) -> int:
    """Every parameter held: the layers, the final norm, the embedding and
    the untied head. Of a `published` dict: the whole model."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    return int(sum(layer_params(config, i) for i in range(n)) + d
               + 2 * config["vocab_size"] * d)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs of one token, forward plus backward, AS THIS CHIP
    computes them: the matrix products of every layer (the held experts'
    expected share: `layer_params(active=True)`), the sliced head's, the
    keys a softmax layer attends (half the sequence in the mean) and a
    linear layer's recurrence (per head S^T k, the rank-one update and S^T
    q: 6 dk dv)."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    h, w, _ = linear_sizes(config)
    matmuls = sum(layer_params(config, i, active=True) for i in range(n))
    matmuls += d * config["vocab_size"]
    attended = 4.0 * config["num_attention_heads"] * config["head_dim"] * (seq + 1) / 2.0
    mixing = sum(6.0 * h * w * w if is_linear(config, i) else attended for i in range(n))
    return 3.0 * (2.0 * matmuls + mixing)


def recurrence_decode_call(config: dict, rows: int, state_itemsize: int = 4,
                           itemsize: int = 2) -> dict:
    """What the linear layers' recurrence of ONE decode step has to do, over
    all linear layers, for `rows` live rows (a parked row's state block is
    neither read nor written). Bytes a row and layer: its state read and
    written once (float32), its conv tail read and written, and its q, k, v,
    its decay (a value a key channel) and beta in and its output out
    (activations). FLOPs a row and layer: 4 dk dv a head (S^T k, the decay
    and the rank-one update, S^T q, a multiply and an add each counted
    once), the two L2 norms and the decay's two products with k and q."""
    h, w, taps = linear_sizes(config)
    layers = linear_layers(config)
    state = 2 * h * w * w * state_itemsize
    tail = 2 * (taps - 1) * 3 * h * w * itemsize
    vectors = (3 * h * w + h * w + h + h * w) * itemsize
    return {
        "bytes": float(rows * layers * (state + tail + vectors)),
        "flops": float(rows * layers * (4 * h * w * w + 8 * h * w)),
    }


def chunk_scan_call(config: dict, tokens: int, state_itemsize: int = 4) -> dict:
    """What the linear layers' scan of ONE prefill chunk has to do, over all
    linear layers, for the chunk's `tokens` REAL tokens, whatever implements
    it (a chunked form does more than this in fewer passes over the state;
    the count is the rule's own). FLOPs a token, head and layer: S^T k, the
    rank-one update and S^T q, 6 dk dv, in the precision the configuration
    states for the state: the chip's peak is its bfloat16 peak, and a
    float32 product takes the MXU three bfloat16 passes, so float32 counts
    three times. Bytes: the row's state block read once and written once a
    layer; q, k, v and the gates are made and used inside the program."""
    h, w, _ = linear_sizes(config)
    layers = linear_layers(config)
    passes = {4: 3, 2: 1}[state_itemsize]
    return {
        "bytes": float(layers * 2 * h * w * w * state_itemsize),
        "flops": float(passes * tokens * layers * 6 * h * w * w),
    }


def moe_decode_call(config: dict, rows: int, assignments: int, experts_hit,
                    itemsize: int = 2) -> dict:
    """What the sparse MLPs of ONE decode step have to do. `rows` rows are
    live, `assignments` (row, expert) pairs were computed here over all
    layers, and `experts_hit` lists, per layer, the distinct held experts
    with at least one row. Bytes: the weights of the experts HIT, read once,
    plus each layer's router. NOT the shared expert's: its products are
    XLA's own, which streams their weights in under EARLIER operations, so
    part of that stream's time lies outside the `moe/...` scopes the reader
    divides by (a trace of this cell showed it: PERF.md section 5); the
    routed experts' stream is the grouped kernel's own copies, inside
    `moe/experts`. The share so reads low by what of the shared expert's
    time IS under its scope, never high. FLOPs: three products an
    assignment, the shared expert and the router for every live row."""
    d, e = config["hidden_size"], routed_experts(config)
    shared = config["n_shared_experts"] * expert_params(config)
    layers = len(experts_hit)
    weights = sum(experts_hit) * expert_params(config) + layers * d * e
    return {
        "bytes": float(weights * itemsize),
        "flops": 2.0 * (assignments * expert_params(config)
                        + rows * layers * (shared + d * e)),
    }
