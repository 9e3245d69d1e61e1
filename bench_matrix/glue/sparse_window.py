"""Glue for a decoder with a per-layer pattern (window and full attention
with per-layer head counts, a per-head output gate, dense and dropless
sparse MLPs with a shared expert), as the program's `TransformerLM` builds
it from `TransformerConfig.layers`: the configuration's Hugging Face keys on
one side, the program's constructor keywords and parameter names on the
other. Pairs with `reference/sparse_window.py`, whose layer dict it fills.
The operation and byte counts of this kind's two roofline metrics live here
too, beside the shapes they are counted from."""

from __future__ import annotations

import jax

from .. import spec
from ..modelglue import DTYPES

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def _rope(config: dict, kind: str):
    from pytorch_distributed_example_tpu.models.transformer import RopeSpec

    r = dict(config["rope_parameters"][kind])
    yarn = None
    if r["rope_type"] == "yarn":
        yarn = (float(r["factor"]), int(r["original_max_position_embeddings"]),
                float(r["beta_fast"]), float(r["beta_slow"]), float(r["attention_factor"]))
    elif r["rope_type"] != "default":
        raise spec.SpecError(f"rope_type {r['rope_type']!r} is not carried")
    return RopeSpec(float(r["rope_theta"]),
                    float(r.get("partial_rotary_factor", 1.0)), yarn)


def build_model(config: dict, max_seq_len: int, remat: bool):
    """The program's model object at the configuration's sizes."""
    from pytorch_distributed_example_tpu.models.transformer import (
        LayerSpec, TransformerConfig, TransformerLM,
    )

    n = config["num_hidden_layers"]
    per_layer = [config[k] for k in
                 ("layer_types", "num_attention_heads_per_layer", "mlp_layer_types")]
    if any(len(v) != n for v in per_layer):
        raise spec.SpecError("a per-layer list is not num_hidden_layers long")
    if config.get("moe_apply_router_weight_on_input"):
        raise spec.SpecError("router weights on the expert INPUT are not carried")
    ropes = {kind: _rope(config, kind) for kind in set(config["layer_types"])}
    layers = tuple(
        LayerSpec(KINDS[kind], heads, ropes[kind], mlp)
        for kind, heads, mlp in zip(*per_layer)
    )
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"], n_layers=n,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_size=config["head_dim"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=config["rms_norm_eps"], layers=layers,
        window=config["sliding_window"], attn_gate=bool(config["gating"]),
        rope_pairs="halves", sparse_experts=config["num_experts"],
        sparse_top_k=config["num_experts_per_tok"],
        sparse_d_ff=config["moe_intermediate_size"],
        shared_d_ff=config["shared_expert_intermediate_size"],
        routed_scale=config["moe_routed_scaling_factor"],
        causal=True, use_flash=False, remat=remat,
        dtype=DTYPES[config["dtype"]["activations"]],
    )
    return TransformerLM(cfg)


# the reference's name for a layer's matrix -> where the program keeps it
ATTENTION = {
    "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "w_head_gate": ("attn", "head_gate", "kernel"),
    "attn_norm": ("attn_norm", "scale"), "mlp_norm": ("mlp_norm", "scale"),
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

        Top-k is discontinuous: a bfloat16 system and a float32 reference
        pick another 8th expert wherever two scores lie within rounding,
        and a comparison of logits then reads the flips and little else.
        The reference therefore asks which experts the system ran, and
        takes a token's where its own scores cannot tell them from its own
        choice (`reference/sparse_window.py::sparse_mlp`). The engine
        hands out no routing, so the sequence is replayed here through
        the same model call the engine's `prefill_chunk` program makes
        (`serve/decode.py::paged_programs`: a chunk of
        `prefill_chunk_tokens` at a time into a paged cache of both
        kinds, same chunk, table and block shapes; a pool of one row),
        with the sown `moe_chosen` fetched. Whole chunks only: what is
        left over (the positions the check decodes) keeps -1."""
        import functools

        import jax.numpy as jnp
        import numpy as np

        from pytorch_distributed_example_tpu.serve.cache import PagedKVCache

        shape = config["model"]["check"]["replay"]
        chunk = shape["prefill_chunk_tokens"]
        model = build_model(config, shape["max_seq_len"], remat=False)
        sparse = model.cfg.sparse_layers
        cache = PagedKVCache(model, 1, block_size=shape["block_size"], chunk_tokens=chunk)
        slot = cache.allocate()

        @functools.partial(jax.jit, donate_argnums=(1,))
        def chosen_in_chunk(params, tree, tokens, tables, start):
            _, out = model.apply(
                {"params": params, "cache": tree}, tokens, decode=True,
                positions=jnp.asarray(start, jnp.int32)[None], block_tables=tables,
                mutable=["cache", "intermediates"], row_mask=tokens >= 0,
            )
            return out["cache"], [
                out["intermediates"][f"layers_{i}"]["mlp"]["moe_chosen"][0][0]
                for i in sparse
            ]

        tokens = np.asarray(tokens, np.int32)
        told = {i: np.full((len(tokens), model.cfg.sparse_top_k), -1, np.int32)
                for i in sparse}
        for start in range(0, len(tokens) - chunk + 1, chunk):
            cache.ensure_blocks(slot, start + chunk - 1, start)
            cache.tree, chosen = chosen_in_chunk(
                self.params, cache.tree, jnp.asarray(tokens[None, start:start + chunk]),
                cache.tables(slice(slot, slot + 1)), start,
            )
            for i, c in zip(sparse, chosen):
                told[i][start:start + chunk] = np.asarray(c)
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

def expert_params(config: dict) -> int:
    """One routed expert: gate, up and down of a SwiGLU."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: dict, i: int, active: bool = False) -> int:
    """Matmul parameters of layer i: all it holds, or with `active` those a
    token meets (its `num_experts_per_tok` routed experts, not all)."""
    d, dh = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads_per_layer"][i], config["num_key_value_heads"]
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d + d * h * bool(config["gating"])
    if config["mlp_layer_types"][i] == "dense":
        return attn + 3 * d * config["intermediate_size"]
    routed = config["num_experts_per_tok"] if active else config["num_experts"]
    return (attn + routed * expert_params(config) + d * config["num_experts"]
            + 3 * d * config["shared_expert_intermediate_size"])


def param_count(config: dict, active: bool = False) -> int:
    """Every parameter (or every one a token meets): the layers' matmuls,
    two norms a layer, the final norm, the embedding and the untied head."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    return (sum(layer_params(config, i, active) for i in range(n)) + 2 * n * d + d
            + 2 * config["vocab_size"] * d)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs of one training token, forward plus backward, over the
    parameters a token is multiplied by (the embedding is a lookup) and the
    keys it attends: all before it in a full layer, at most the window in a
    window layer, averaged over the positions of a `seq`-token sequence."""
    n, d, w = config["num_hidden_layers"], config["hidden_size"], config["sliding_window"]
    matmuls = sum(layer_params(config, i, active=True) for i in range(n))
    matmuls += d * config["vocab_size"]
    attention = 0.0
    for i in range(n):
        width = config["num_attention_heads_per_layer"][i] * config["head_dim"]
        keys = (seq + 1) / 2.0
        if config["layer_types"][i] == "sliding_attention" and seq > w:
            keys = (w * (w + 1) / 2.0 + (seq - w) * w) / seq
        attention += 4.0 * keys * width
    return 3.0 * (2.0 * matmuls + attention)


def moe_decode_call(config: dict, rows: int, assignments: int, experts_hit,
                    itemsize: int = 2) -> dict:
    """What the sparse MLPs of ONE decode step have to do. `rows` rows are
    live, `assignments` (row, expert) pairs were computed over all sparse
    layers, and `experts_hit` lists, per sparse layer, the distinct experts
    with at least one row. Bytes: the weights of the experts HIT, read once
    (an expert no row chose is not needed), plus each layer's shared expert
    and router; the rows themselves are under 1 % of that and left out.
    FLOPs: three products an assignment, the shared expert and the router
    for every live row."""
    d, e = config["hidden_size"], config["num_experts"]
    shared = 3 * d * config["shared_expert_intermediate_size"]
    layers = len(experts_hit)
    weights = sum(experts_hit) * expert_params(config) + layers * (shared + d * e)
    return {
        "bytes": float(weights * itemsize),
        "flops": 2.0 * (assignments * expert_params(config)
                        + rows * layers * (shared + d * e)),
    }


def window_decode_call(config: dict, keys, itemsize: int = 2) -> dict:
    """What the paged decode attention calls of ONE engine step have to do,
    over all layers. `keys` holds, for every row that decodes in the step,
    the keys it has cached and the one the step writes; a full layer reads
    them all, a window layer the last `sliding_window` of them, each with
    its own query-head count. A parked row is not in `keys`. Bytes: the
    attended K and V read once (the KV heads; a group's query heads share
    them); FLOPs: QK^T and PV for every query head."""
    kv, dh, w = config["num_key_value_heads"], config["head_dim"], config["sliding_window"]
    nbytes = flops = 0.0
    for kind, heads in zip(config["layer_types"], config["num_attention_heads_per_layer"]):
        n = float(sum(min(k, w) if kind == "sliding_attention" else k for k in keys))
        nbytes += n * kv * dh * 2 * itemsize
        flops += 4.0 * n * heads * dh
    return {"bytes": nbytes, "flops": flops}
