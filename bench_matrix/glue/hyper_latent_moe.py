"""Glue for a decoder whose blocks carry `hc_mult` residual streams mixed by
Sinkhorn-normalised hyper-connections around multi-head latent attention
(YaRN on its rope) and leading dense layers, then a sigmoid-routed dropless
MoE with a shared expert whose router chooses with a correction bias, every
expert held: the configuration's Hugging Face keys on one side, the
program's constructor keywords and parameter names on the other. Pairs with
`reference/hyper_latent_moe.py`, whose layer dict it fills. What it counts
of latent attention is `glue/latent_moe.py`'s (the same shapes at other
numbers); the streams' bytes (`hc_call`) live here."""

from __future__ import annotations

import math

import jax

from .. import spec
from ..modelglue import DTYPES
from .latent_moe import (  # noqa: F401  (the readers and the trainers' FLOPs find them here)
    _at, attention_params, expert_params, latent_chunk_call, latent_decode_call, pair_flops,
)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def build_model(config: dict, max_seq_len: int, remat: bool):
    """The program's model object at the configuration's sizes."""
    from pytorch_distributed_example_tpu.models.transformer import (
        LayerSpec, RopeSpec, TransformerConfig, TransformerLM,
    )

    s = config["rope_scaling"]
    refused = {
        "attention_bias": config["attention_bias"],
        "tie_word_embeddings": config["tie_word_embeddings"],
        "norm_topk_prob: false": not config["norm_topk_prob"],
        f"hidden_act {config['hidden_act']!r}": config["hidden_act"] != "silu",
        f"scoring_func {config['scoring_func']!r}": config["scoring_func"] != "sigmoid",
        f"topk_method {config['topk_method']!r}": config["topk_method"] != "noaux_tc",
        "router groups above one (n_group, topk_group)":
            (config["n_group"], config["topk_group"]) != (1, 1),
        f"rope_scaling type {s['type']!r}": s["type"] != "yarn",
        "key/value heads that differ from the query heads":
            config["num_key_value_heads"] != config["num_attention_heads"],
        "a share of the routed experts":
            config["n_routed_experts"] != config["published"]["n_routed_experts"],
    }
    for what, said in refused.items():
        if said:
            raise spec.SpecError(f"{what} is not carried")
    n, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    # `assumed` (6): adjacent pairs; cos and sin carry the ratio of the two
    # mscales, the softmax scale the square of `mscale_all_dim`'s
    rope = RopeSpec(
        float(config["rope_theta"]),
        yarn=(float(s["factor"]), s["original_max_position_embeddings"],
              float(s["beta_fast"]), float(s["beta_slow"]),
              yarn_mscale(s["factor"], s["mscale"]) / yarn_mscale(
                  s["factor"], s["mscale_all_dim"])),
        softmax_factor=yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2,
    )
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"], n_layers=n,
        n_heads=config["num_attention_heads"], d_ff=config["intermediate_size"],
        max_seq_len=max_seq_len, norm_eps=config["rms_norm_eps"],
        layers=tuple(
            LayerSpec("latent", rope=rope, mlp="dense" if i < dense else "sparse")
            for i in range(n)
        ),
        latent_q_rank=config["q_lora_rank"], latent_kv_rank=config["kv_lora_rank"],
        latent_nope_dim=config["qk_nope_head_dim"],
        latent_rope_dim=config["qk_rope_head_dim"], latent_v_dim=config["v_head_dim"],
        sparse_score="sigmoid", sparse_choice_bias=True,
        sparse_experts=config["n_routed_experts"],
        sparse_top_k=config["num_experts_per_tok"],
        sparse_d_ff=config["moe_intermediate_size"],
        shared_d_ff=config["n_shared_experts"] * config["moe_intermediate_size"],
        routed_scale=config["routed_scaling_factor"],
        hc_mult=config["hc_mult"], hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_clamp=(float(config["mhc_h_res_clamp_min"]), float(config["mhc_h_res_clamp_max"])),
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
    "attn_norm": ("attn_norm", "scale"), "mlp_norm": ("mlp_norm", "scale"),
    **{f"{hc}_{part}": (hc, part)
       for hc in ("hc_attn", "hc_mlp") for part in ("phi", "alpha", "bias")},
}
DENSE = {
    "w_gate": ("mlp", "gate_proj", "kernel"), "w_up": ("mlp", "up_proj", "kernel"),
    "w_down": ("mlp", "down_proj", "kernel"),
}
SPARSE = {
    "router": ("mlp", "router"), "router_bias": ("mlp", "router_bias"),
    "experts_gate": ("mlp", "experts_gate"),
    "experts_up": ("mlp", "experts_up"), "experts_down": ("mlp", "experts_down"),
    "shared_gate": ("mlp", "shared_expert", "gate_proj", "kernel"),
    "shared_up": ("mlp", "shared_expert", "up_proj", "kernel"),
    "shared_down": ("mlp", "shared_expert", "down_proj", "kernel"),
}


class Layers:
    """What `reference_parts` hands the reference as its layers: iterated,
    one layer's weights at a time in the reference's names; `out`, the three
    arrays of the streams' end; asked (`system_routing`), the experts the
    SYSTEM's sparse layers chose for a sequence."""

    def __init__(self, params, put):
        self.params, self.put = params, put
        self.count = sum(1 for k in params if k.startswith("layers_"))

    def __iter__(self):
        for i in range(self.count):
            blk = self.params[f"layers_{i}"]
            names = dict(ATTENTION, **(SPARSE if "router" in blk["mlp"] else DENSE))
            yield {ours: self.put(_at(blk, path)) for ours, path in names.items()}

    @property
    def out(self):
        return tuple(self.put(self.params["hc_out"][part]) for part in ("phi", "alpha", "bias"))

    def system_routing(self, tokens, config: dict) -> dict:
        """{sparse layer: (len(tokens), top_k) int32}: the experts the program's
        model chose for each token, served as the cell's runner serves it.

        The engine hands out no routing, so the sequence is replayed here
        through the model call the engine's `prefill_chunk` program makes
        (`serve/decode.py::paged_programs`; `glue/latent_moe.py` says why):
        chunks of `prefill_chunk_tokens` into a paged latent cache of the
        engine's block and table shapes (a pool of one row), each as the
        engine cuts it: what is left of the prompt in the bucket that covers
        it, padded with token id -1. With `replay.attached_tokens` = A the
        prompt's first A tokens were prefilled by ANOTHER request of the same
        length, in chunks from 0, and attached (`runners/serve_prefix.py`):
        they are told from such a pass (a row depends on no later token, so
        the chunk that crosses A holds this sequence's own tokens behind A
        where that request held its own), and the rest from chunks that
        start at A. The last `decoded_tail` tokens were decoded by the `step`
        program: each is replayed as it calls the model, at the engine's
        `slots` rows (one live at its length, the rest parked: another
        shape rounds elsewhere in bfloat16 and chooses other experts)."""
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
        sparse, lanes, bs = model.cfg.sparse_layers, shape["slots"], shape["block_size"]
        cache = PagedKVCache(model, lanes, shape["max_seq_len"] // bs, bs, chunk_tokens=chunk)
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

        @functools.partial(jax.jit, donate_argnums=(1,))
        def chosen_in_step(params, tree, last, tables, lengths):
            # the model call of `paged_programs`' `step`, at the engine's shapes:
            # every slot one token at its length, a row live where it holds a block
            blocks = jax.tree_util.tree_leaves(tree["layers_0"])[0].shape[0]
            _, out = model.apply(
                {"params": params, "cache": tree}, last[:, None], decode=True,
                positions=lengths, block_tables=tables,
                mutable=["cache", "intermediates"],
                row_mask=jnp.any(tables < blocks, axis=1, keepdims=True),
            )
            return out["cache"], [
                out["intermediates"][f"layers_{i}"]["mlp"]["moe_chosen"][0][slot, 0]
                for i in sparse
            ]

        tokens = np.asarray(tokens, np.int32)
        n_prompt = len(tokens) - shape["decoded_tail"]
        attached = min(shape.get("attached_tokens", 0), n_prompt)
        told = {i: np.full((len(tokens), model.cfg.sparse_top_k), -1, np.int32)
                for i in sparse}
        # (first position prefilled, rows told from the pass): the request
        # that indexed the head, then the one that attached it
        for first, rows in ((0, (0, attached)), (attached, (attached, n_prompt))):
            start = first
            while start < rows[1]:
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
                lo, hi = max(start, rows[0]), min(end, rows[1])
                for i, c in zip(sparse, chosen):
                    told[i][lo:hi] = np.asarray(c)[lo - start:hi - start]
                start = end
        last = np.zeros(lanes, np.int32)
        lengths = np.full(lanes, shape["max_seq_len"] - 1, np.int32)  # parked lanes
        for at in range(n_prompt, len(tokens)):
            cache.ensure_blocks(slot, at, at)
            last[slot], lengths[slot] = tokens[at], at
            cache.tree, chosen = chosen_in_step(
                self.params, cache.tree, jnp.asarray(last), cache.tables(), jnp.asarray(lengths),
            )
            for i, c in zip(sparse, chosen):
                told[i][at] = np.asarray(c)
        return told


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

def hc_params(config: dict, collapse: bool = False) -> int:
    """One sublayer's maps: Phi (nC x (2n + n^2)), three scalars, 2n + n^2
    offsets; the streams' end: Phi (nC x n), one scalar, n offsets."""
    n = config["hc_mult"]
    width = n if collapse else 2 * n + n * n
    return n * config["hidden_size"] * width + (1 if collapse else 3) + width


def layer_params(config: dict, i: int, active: bool = False) -> int:
    """Every parameter of layer i with its two norms and its two sublayers'
    maps: all it holds, or with `active` those a token meets (its
    `num_experts_per_tok` routed experts)."""
    d = config["hidden_size"]
    base = attention_params(config) + 2 * d + 2 * hc_params(config)
    if i < config["first_k_dense_replace"]:
        return base + 3 * d * config["intermediate_size"]
    experts = config["n_routed_experts"]
    routed = config["num_experts_per_tok"] if active else experts
    return (base + routed * expert_params(config) + d * experts + experts
            + config["n_shared_experts"] * expert_params(config))


def param_count(config: dict, active: bool = False) -> int:
    """Every parameter held (or every one a token meets): the layers, the
    streams' end, the final norm, the embedding and the untied head. Of a
    `published` dict: the whole model without its multi-token-prediction
    module."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    return (sum(layer_params(config, i, active) for i in range(n))
            + hc_params(config, collapse=True) + d + 2 * config["vocab_size"] * d)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs of one training token, forward plus backward: the
    parameters a token is multiplied by (the embedding is a lookup, the
    norms are not products; the maps' Phi are) and, NON-absorbed as a
    trainer would run it, the keys it attends."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    matmuls = sum(layer_params(config, i, active=True) for i in range(n)) + d * config["vocab_size"]
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    attention = n * 2.0 * config["num_attention_heads"] * width * (seq + 1) / 2.0
    return 3.0 * (2.0 * matmuls + attention)


def hc_call(config: dict, tokens: int, itemsize: int = 2) -> dict:
    """The bytes the hyper-connection maps of `tokens` tokens have to move,
    over all layers, as the EQUATIONS need them and whatever implements
    them: a sublayer reads the n streams (for rho, the product with Phi and
    the mixture: once), writes the mixture u, reads the sublayer's output y,
    reads the streams again (they cannot be kept across the sublayer) and
    writes the n new ones: (3n + 2) C values a token = 100352 B at n = 4,
    C = 3584 in bfloat16; the streams' end reads n and writes one. Phi and
    the maps themselves are a few values a token and are left out; the
    FLOPs (2 nC (2n + n^2) a token a sublayer) are far under the ridge."""
    n, c = config["hc_mult"], config["hidden_size"]
    sublayers = 2 * config["num_hidden_layers"]
    values = sublayers * (3 * n + 2) * c + (n + 1) * c
    flops = sublayers * 2 * n * c * (2 * n + n * n) + 2 * n * c * n
    return {"bytes": float(tokens * values * itemsize), "flops": float(tokens * flops)}
