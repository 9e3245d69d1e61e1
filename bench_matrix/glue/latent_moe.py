"""Glue for a decoder with multi-head latent attention, sandwich norms,
leading dense layers and a sigmoid-routed dropless MoE with a shared expert
of which this chip holds a share, as the program's `TransformerLM` builds
it from `TransformerConfig.layers` with "latent" layers: the configuration's
Hugging Face keys on one side, the program's constructor keywords and
parameter names on the other. Pairs with `reference/latent_moe.py`, whose
layer dict it fills. The operation and byte counts of this kind's two
roofline metrics (`latent_decode_call`, `latent_chunk_call`) live here too,
beside the shapes they are counted from."""

from __future__ import annotations

import jax

from .. import spec
from ..modelglue import DTYPES


def routed_experts(config: dict) -> int:
    """The router's width: the published count, whatever share is held."""
    return config.get("published", config)["n_routed_experts"]


def build_model(config: dict, max_seq_len: int, remat: bool):
    """The program's model object at the configuration's sizes."""
    from pytorch_distributed_example_tpu.models.transformer import (
        LayerSpec, RopeSpec, TransformerConfig, TransformerLM,
    )

    refused = {
        "attention_bias": config["attention_bias"],
        "tie_word_embeddings": config["tie_word_embeddings"],
        "norm_topk_prob: false": not config["norm_topk_prob"],
        "sandwich_norm: false": not config["sandwich_norm"],
        f"hidden_act {config['hidden_act']!r}": config["hidden_act"] != "silu",
        "key/value heads that differ from the query heads":
            config["num_key_value_heads"] != config["num_attention_heads"],
    }
    for what, said in refused.items():
        if said:
            raise spec.SpecError(f"{what} is not carried")
    n, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    rope = RopeSpec(float(config["rope_theta"]))  # `assumed` (3), (4): adjacent pairs, no YaRN
    held = config["n_routed_experts"]
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"], n_layers=n,
        n_heads=config["num_attention_heads"], d_ff=config["intermediate_size"],
        max_seq_len=max_seq_len, norm_eps=config["rms_norm_eps"],
        layers=tuple(
            LayerSpec("latent", rope=rope, mlp="dense" if i < dense else "sparse")
            for i in range(n)
        ),
        sandwich_norm=True,  # `assumed` (2)
        latent_q_rank=config["q_lora_rank"], latent_kv_rank=config["kv_lora_rank"],
        latent_nope_dim=config["qk_nope_head_dim"],
        latent_rope_dim=config["qk_rope_head_dim"], latent_v_dim=config["v_head_dim"],
        # `assumed` (1); the router keeps its published width, the chip
        # holds the leading `n_routed_experts` of them (`deployment`)
        sparse_score="sigmoid", sparse_experts=routed_experts(config),
        experts_held=None if held == routed_experts(config) else (0, held),
        sparse_top_k=config["num_experts_per_tok"],
        sparse_d_ff=config["moe_intermediate_size"],
        shared_d_ff=config["n_shared_experts"] * config["moe_intermediate_size"],
        routed_scale=config["routed_scaling_factor"],
        causal=True, use_flash=False, remat=remat,
        dtype=DTYPES[config["dtype"]["activations"]],
    )
    return TransformerLM(cfg)


# the reference's name for a layer's array -> where the program keeps it
ATTENTION = {
    "w_qa": ("latent_attn", "q_a_proj", "kernel"), "q_a_norm": ("latent_attn", "q_a_norm", "scale"),
    "w_qb": ("latent_attn", "q_b_proj", "kernel"),
    "w_kva": ("latent_attn", "kv_a_proj", "kernel"),
    "kv_a_norm": ("latent_attn", "kv_a_norm", "scale"),
    "w_kvb": ("latent_attn", "kv_b_proj"), "w_o": ("latent_attn", "o_proj", "kernel"),
    "attn_norm": ("attn_norm", "scale"), "attn_post_norm": ("attn_post_norm", "scale"),
    "mlp_norm": ("mlp_norm", "scale"), "mlp_post_norm": ("mlp_post_norm", "scale"),
}
DENSE = {
    "w_gate": ("mlp", "gate_proj", "kernel"), "w_up": ("mlp", "up_proj", "kernel"),
    "w_down": ("mlp", "down_proj", "kernel"),
}
SPARSE = {
    "router": ("mlp", "router"), "experts_gate": ("mlp", "experts_gate"),
    "experts_up": ("mlp", "experts_up"), "experts_down": ("mlp", "experts_down"),
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
            names = dict(ATTENTION, **(SPARSE if "router" in blk["mlp"] else DENSE))
            yield {ours: self.put(_at(blk, path)) for ours, path in names.items()}

    def system_routing(self, tokens, config: dict) -> dict:
        """{sparse layer: (len(tokens), top_k) int32}: the experts the
        program's model chose for each token when the sequence is
        prefilled the way the engine under test prefills it, -1 where
        the sequence was not replayed.

        Sigmoid top-8 of 256 flips on rounding as softmax top-8 does
        (`glue/sparse_window.py::Layers.system_routing` says why the
        reference asks). The engine hands out no routing, so the prompt is
        replayed here through the same model call the engine's
        `prefill_chunk` program makes (`serve/decode.py::paged_programs`):
        chunks of `prefill_chunk_tokens` into a paged latent cache of the
        engine's block and table shapes (a pool of one row), and then the
        LAST chunk as the engine cuts it: what is left of the prompt in
        the bucket that covers it, padded with token id -1, which the
        program tells from tokens. The check's prompt ends inside a bucket
        and the rows it compares lie in that chunk. The sequence's last
        `decoded_tail` tokens were decoded one at a time and keep -1."""
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

def attention_params(config: dict) -> int:
    """One latent attention block: the two low-rank query matrices and the
    norm between them, the joint down-projection and the latent's norm, the
    per-head up-projections, the output matrix."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    q, r = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rot, dv = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    return (d * q + q + q * h * (nope + rot) + d * (r + rot) + r
            + r * h * (nope + dv) + h * dv * d)


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down of a SwiGLU."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: dict, i: int, active: bool = False) -> int:
    """Every parameter of layer i with its four norms: all it holds here
    (`n_routed_experts` experts), or with `active` those a token meets
    (its `num_experts_per_tok` routed experts)."""
    d = config["hidden_size"]
    base = attention_params(config) + 4 * d
    if i < config["first_k_dense_replace"]:
        return base + 3 * d * config["intermediate_size"]
    routed = config["num_experts_per_tok"] if active else config["n_routed_experts"]
    return (base + routed * expert_params(config) + d * routed_experts(config)
            + config["n_shared_experts"] * expert_params(config))


def param_count(config: dict, active: bool = False) -> int:
    """Every parameter held (or every one a token meets): the layers, the
    final norm, the embedding and the untied head. Of a `published` dict:
    the whole model without its multi-token-prediction module."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    return (sum(layer_params(config, i, active) for i in range(n)) + d
            + 2 * config["vocab_size"] * d)


def pair_flops(config: dict) -> int:
    """FLOPs of one (query, key) pair in one layer, absorbed: every head
    scores the latent and the shared rotary key (`kv_lora_rank` +
    `qk_rope_head_dim` values) and sums the latent (`kv_lora_rank`)."""
    r, rot = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return 2 * config["num_attention_heads"] * ((r + rot) + r)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs of one training token, forward plus backward: the
    parameters a token is multiplied by (the embedding is a lookup, the
    norms are not products) and, NON-absorbed as a trainer would run it, the
    keys it attends at `qk_nope + qk_rope` a score and `v_head_dim` a value."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    matmuls = sum(layer_params(config, i, active=True) for i in range(n)) + d * config["vocab_size"]
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    attention = n * 2.0 * config["num_attention_heads"] * width * (seq + 1) / 2.0
    return 3.0 * (2.0 * matmuls + attention)


def latent_decode_call(config: dict, keys: float, itemsize: int = 2,
                       distinct: float = None) -> dict:
    """What the latent decode attention calls of ONE engine step have to
    do, over all layers. `keys` is the keys attended, summed over the rows
    that decode in the step (each row's cached keys and the one the step
    writes; a parked row has none); `distinct` the same with a key that
    several rows attend counted once (`flops.distinct_keys`; None: no two
    rows hold one block). Bytes: a distinct key's PUBLISHED row (latent and
    rotary key: 576 values) read once, whatever the pool holds beside it;
    FLOPs: `pair_flops` a (row, key) pair."""
    n = config["num_hidden_layers"]
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return {"bytes": float(n * (keys if distinct is None else distinct) * row * itemsize),
            "flops": float(n * keys * pair_flops(config))}


def chunk_pair_flops(config: dict, keys: int, pairs: int) -> int:
    """FLOPs of one layer's attention over `pairs` (query, key) pairs on
    `keys` cached latents, in the cheaper of the two forms the arithmetic
    allows. Absorbed: `pair_flops` a pair. Up-projected: every key's heads
    made from its latent once (`kv_lora_rank` x heads x (`qk_nope_head_dim`
    + `v_head_dim`) products), then a pair scores `qk_nope_head_dim +
    qk_rope_head_dim` values a head and sums `v_head_dim`: cheaper from
    about 170 queries a key up, 147.5 kFLOP a pair against 278.5 at 128
    heads and a 512-token chunk over a long context."""
    h, r = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rot, dv = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    up = keys * 2 * r * h * (nope + dv) + pairs * 2 * h * (nope + rot + dv)
    return min(pairs * pair_flops(config), up)


def latent_chunk_call(config: dict, start: int, tokens: int, itemsize: int = 2) -> dict:
    """What the latent chunk attention calls of ONE prefill chunk have to
    do, over all layers: `tokens` real queries at positions `start ...`,
    each attending the keys at positions <= its own (padding attends
    nothing that counts). FLOPs: `chunk_pair_flops` of the causal pairs,
    the cheaper form at this chunk's own size; bytes: the published rows of
    the `start + tokens` keys read ONCE a chunk (the least any blocking of
    the queries can read)."""
    n = config["num_hidden_layers"]
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    pairs = tokens * start + tokens * (tokens + 1) // 2
    return {"bytes": float(n * (start + tokens) * row * itemsize),
            "flops": float(n * chunk_pair_flops(config, start + tokens, pairs))}
