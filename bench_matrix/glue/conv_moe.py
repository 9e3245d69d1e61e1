"""Glue for a decoder that mixes gated short-convolution layers with
grouped-query attention (per-head q/k norm, 64-wide heads), over leading
dense layers and a sigmoid-routed dropless MoE with an expert bias, no
shared expert and a tied head, of which this chip holds a share of the
experts, as the program's `TransformerLM` builds it from
`TransformerConfig.layers` with "conv" layers: the configuration's Hugging
Face keys on one side, the program's constructor keywords and parameter
names on the other. Pairs with `reference/conv_moe.py`, whose layer dict it
fills. The operation and byte counts of this kind's roofline metrics
(`moe_decode_call`, `gqa_decode_call`) live here
too, beside the shapes they are counted from."""

from __future__ import annotations

import jax

from .. import spec
from ..modelglue import DTYPES

KINDS = {"full_attention": "full", "conv": "conv"}


def routed_experts(config: dict) -> int:
    """The router's width: the published count, whatever share is held."""
    return config.get("published", config)["num_experts"]


def head_dim(config: dict) -> int:
    """`assumed`: the source gives no head size; hidden / heads."""
    return config["hidden_size"] // config["num_attention_heads"]


def build_model(config: dict, max_seq_len: int, remat: bool):
    """The program's model object at the configuration's sizes."""
    from pytorch_distributed_example_tpu.models.transformer import (
        LayerSpec, RopeSpec, TransformerConfig, TransformerLM,
    )

    lacks = {"conv_taps", "qk_head_norm", "tie_embeddings", "sparse_norm_eps"} - set(
        TransformerConfig.__dataclass_fields__)
    if lacks:  # a program from before the conv mixer: refused here, at once
        raise spec.SpecError(f"this program's TransformerConfig has no {sorted(lacks)}")
    refused = {
        "conv_bias": config["conv_bias"],
        "norm_topk_prob: false": not config["norm_topk_prob"],
        "use_expert_bias: false": not config["use_expert_bias"],
        "layer_types that is not num_hidden_layers long":
            len(config["layer_types"]) != config["num_hidden_layers"],
        "a hidden size the heads do not divide":
            config["hidden_size"] % config["num_attention_heads"],
    }
    for what, said in refused.items():
        if said:
            raise spec.SpecError(f"{what} is not carried")
    n, dense = config["num_hidden_layers"], config["num_dense_layers"]
    rope = RopeSpec(float(config["rope_theta"]))
    held = config["num_experts"]
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"], n_layers=n,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=config["norm_eps"],
        layers=tuple(
            LayerSpec(KINDS[kind], rope=rope, mlp="dense" if i < dense else "sparse")
            for i, kind in enumerate(config["layer_types"])
        ),
        # `assumed`: halves layout, a norm a head, the embedding as the head
        rope_pairs="halves", qk_head_norm=True, tie_embeddings=True,
        conv_taps=config["conv_L_cache"],
        # the router keeps its published width, the chip holds the leading
        # `num_experts` of them (`deployment`)
        sparse_score="sigmoid", sparse_choice_bias=True, sparse_norm_eps=1e-6,
        sparse_experts=routed_experts(config),
        experts_held=None if held == routed_experts(config) else (0, held),
        sparse_top_k=config["num_experts_per_tok"],
        sparse_d_ff=config["moe_intermediate_size"], shared_d_ff=0,
        routed_scale=float(config["routed_scaling_factor"]),
        causal=True, use_flash=False, remat=remat,
        dtype=DTYPES[config["dtype"]["activations"]],
    )
    return TransformerLM(cfg)


# the reference's name for a layer's array -> where the program keeps it
NORMS = {"operator_norm": ("attn_norm", "scale"), "ffn_norm": ("mlp_norm", "scale")}
CONV = {
    "w_in": ("gated_conv", "in_proj", "kernel"), "conv": ("gated_conv", "conv"),
    "w_out": ("gated_conv", "out_proj", "kernel"),
}
ATTENTION = {
    "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "q_norm": ("attn", "q_norm", "scale"), "k_norm": ("attn", "k_norm", "scale"),
}
DENSE = {
    "w_gate": ("mlp", "gate_proj", "kernel"), "w_up": ("mlp", "up_proj", "kernel"),
    "w_down": ("mlp", "down_proj", "kernel"),
}
SPARSE = {
    "router": ("mlp", "router"), "router_bias": ("mlp", "router_bias"),
    "experts_gate": ("mlp", "experts_gate"), "experts_up": ("mlp", "experts_up"),
    "experts_down": ("mlp", "experts_down"),
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
            names = dict(
                NORMS, **(CONV if "gated_conv" in blk else ATTENTION),
                **(SPARSE if "router" in blk["mlp"] else DENSE))
            yield {ours: self.put(_at(blk, path)) for ours, path in names.items()}

    def system_routing(self, tokens, config: dict) -> dict:
        """{sparse layer: (len(tokens), top_k) int32}: the experts the
        program's model chose for each token when the sequence is
        prefilled the way the engine under test prefills it, -1 where
        the sequence was not replayed.

        Sigmoid top-4 of 32 by score plus bias flips on rounding wherever
        the fourth and fifth biased scores lie within bfloat16's
        accumulated rounding (`glue/sparse_window.py::Layers.system_routing`
        says why the reference asks). The engine hands out no routing, so
        the prompt is replayed here through the same model call the
        engine's `prefill_chunk` program makes (`serve/decode.py::
        paged_programs`): chunks of `prefill_chunk_tokens` into a paged
        cache of the engine's block and table shapes (a pool of one row,
        with its one state block), and then the LAST chunk as the engine
        cuts it: what is left of the prompt in the bucket that covers it,
        padded with token id -1, which the program tells from tokens. The
        check's prompt ends inside a bucket and the rows it compares lie in
        that chunk. The sequence's last `decoded_tail` tokens were decoded
        one at a time and keep -1."""
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
    asked for. The head is TIED: the output matrix is the embedding's own
    array, transposed; the tree holds no other."""
    p = variables["params"] if "params" in variables else variables
    put = (lambda a: jax.device_put(a, device)) if device is not None else (lambda a: a)
    if "lm_head" in p:
        raise spec.SpecError("this kind's head is the embedding: the tree holds an lm_head")
    embedding = put(p["tok_embed"]["embedding"])
    return embedding, Layers(p, put), put(p["final_norm"]["scale"]), embedding.T


# --- counts from shapes -----------------------------------------------------

def conv_layers(config: dict) -> int:
    return sum(kind == "conv" for kind in config["layer_types"])


def attention_layers(config: dict) -> int:
    return sum(kind == "full_attention" for kind in config["layer_types"])


def mixer_params(config: dict, i: int, matmuls_only: bool = False) -> int:
    """Layer i's operator. Conv: the input projection to three times the
    hidden size, the taps, the output projection. Attention: q, k, v, o
    and the two per-head norms."""
    d, h = config["hidden_size"], head_dim(config)
    if config["layer_types"][i] == "conv":
        return 3 * d * d + d * d + (0 if matmuls_only else config["conv_L_cache"] * d)
    kv = config["num_key_value_heads"] * h
    return 2 * d * d + 2 * d * kv + (0 if matmuls_only else 2 * h)


def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down of a SwiGLU."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held_per_token(config: dict) -> float:
    """Of a token's `num_experts_per_tok` assignments, those that fall to
    the experts THIS chip holds, in expectation over a router that spreads
    its choices evenly: top_k x held / published (4 x 16 / 32 = 2)."""
    return config["num_experts_per_tok"] * config["num_experts"] / routed_experts(config)


def layer_params(config: dict, i: int, active: bool = False) -> float:
    """Every parameter of layer i with its two norms: all it holds here
    (`num_experts` experts, router, bias), or with `active` the matrix
    products a token is multiplied by ON THIS CHIP: the held experts'
    expected share of its assignments (`held_per_token`), not all
    `num_experts_per_tok` of them. The count feeds `serve_mfu_pct`, a share
    of THIS chip's peak; the lesser count is the safe one."""
    d = config["hidden_size"]
    mixer = mixer_params(config, i, matmuls_only=active)
    if i < config["num_dense_layers"]:
        ffn = 3 * d * config["intermediate_size"]
    elif active:
        ffn = held_per_token(config) * expert_params(config) + d * routed_experts(config)
    else:
        ffn = (config["num_experts"] * expert_params(config)
               + d * routed_experts(config) + routed_experts(config))
    return mixer + ffn + (0 if active else 2 * d)


def param_count(config: dict) -> int:
    """Every parameter held: the layers, the final norm and the embedding,
    which is also the head. Of a `published` dict: the whole model."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    return int(sum(layer_params(config, i) for i in range(n)) + d + config["vocab_size"] * d)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs of one token, forward plus backward, AS THIS CHIP
    computes them: the matrix products of every layer (a sparse layer's at
    the held experts' expected share: `layer_params(active=True)`), the
    tied head's product (the embedding is a lookup going in and a product
    coming out), the keys an attention layer attends (half the sequence in
    the mean) and a conv layer's taps and two gates."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    matmuls = sum(layer_params(config, i, active=True) for i in range(n))
    matmuls += d * config["vocab_size"]
    mixing = (attention_layers(config) * 4.0 * d * (seq + 1) / 2.0
              + conv_layers(config) * (2.0 * config["conv_L_cache"] + 2.0) * d)
    return 3.0 * (2.0 * matmuls + mixing)


def moe_decode_call(config: dict, rows: int, assignments: int, experts_hit,
                    itemsize: int = 2) -> dict:
    """What the sparse MLPs of ONE decode step have to do. `rows` rows are
    live, `assignments` (row, expert) pairs were computed here over all
    sparse layers, and `experts_hit` lists, per sparse layer, the distinct
    held experts with at least one row. Bytes: the weights of the experts
    HIT, read once, plus each layer's router; no shared expert. FLOPs:
    three products an assignment, the router for every live row."""
    d, e = config["hidden_size"], routed_experts(config)
    layers = len(experts_hit)
    return {
        "bytes": float((sum(experts_hit) * expert_params(config) + layers * d * e) * itemsize),
        "flops": 2.0 * (assignments * expert_params(config) + rows * layers * d * e),
    }


def gqa_decode_call(config: dict, keys, distinct: int, itemsize: int = 2) -> dict:
    """What the attention layers' cached attention of ONE decode step has
    to do, over all attention layers. `keys` holds, for every row that
    decodes in the step, the keys it attends (its cached tokens and the one
    the step writes); `distinct` the same with a key that several rows'
    tables hold counted once (`flops.distinct_keys`). Bytes: the K and V of
    the distinct keys read once, `num_key_value_heads` heads of `head_dim`
    values as the MODEL has them (whatever layout the pool holds them in);
    FLOPs: QK^T and PV of every query head for every (row, key) pair."""
    h, layers = head_dim(config), attention_layers(config)
    return {
        "bytes": float(layers * distinct * config["num_key_value_heads"] * h * 2 * itemsize),
        "flops": float(layers * 4.0 * sum(keys) * config["num_attention_heads"] * h),
    }
