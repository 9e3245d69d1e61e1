"""Glue for a decoder that mixes linear-attention (Gated DeltaNet) layers
with full softmax attention, as the program's `TransformerLM` builds it from
`TransformerConfig.layers` with "linear" layers: the configuration's Hugging
Face keys on one side, the program's constructor keywords and parameter
names on the other. Pairs with `reference/hybrid_linear.py`, whose layer
dict it fills. The operation and byte count of this kind's roofline metric
(`recurrence_decode_call`) lives here too, beside the shapes it is counted
from."""

from __future__ import annotations

import jax

from .. import spec
from ..modelglue import DTYPES

KINDS = {"full_attention": "full", "linear_attention": "linear"}


def build_model(config: dict, max_seq_len: int, remat: bool):
    """The program's model object at the configuration's sizes."""
    from pytorch_distributed_example_tpu.models.transformer import (
        LayerSpec, RopeSpec, TransformerConfig, TransformerLM,
    )

    n = config["num_hidden_layers"]
    if len(config["layer_types"]) != n:
        raise spec.SpecError("layer_types is not num_hidden_layers long")
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise spec.SpecError("key and value head counts that differ are not carried")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise spec.SpecError("TransformerConfig derives head_dim as d_model / n_heads")
    if config["hidden_act"] != "silu":
        raise spec.SpecError(f"hidden_act {config['hidden_act']!r} is not carried")
    # `assumed` (3): no theta, no rotary embedding; a number rotates halves
    theta = config["rope_parameters"]["rope_theta"]
    rope = RopeSpec(rotary_fraction=0.0) if theta is None else RopeSpec(float(theta))
    cfg = TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"], n_layers=n,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=config["rms_norm_eps"],
        layers=tuple(LayerSpec(KINDS[kind], rope=rope) for kind in config["layer_types"]),
        rope_pairs="halves", post_norm=True, qk_norm=True,  # `assumed` (1), (2)
        linear_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        linear_conv=config["linear_conv_kernel_dim"],
        linear_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        causal=True, use_flash=False, remat=remat,
        dtype=DTYPES[config["dtype"]["activations"]],
    )
    return TransformerLM(cfg)


# the reference's name for a layer's array -> where the program keeps it
SHARED = {
    "attn_norm": ("attn_norm", "scale"), "mlp_norm": ("mlp_norm", "scale"),
    "w_gate": ("mlp", "gate_proj", "kernel"), "w_up": ("mlp", "up_proj", "kernel"),
    "w_down": ("mlp", "down_proj", "kernel"),
}
FULL = {
    "wq": ("attn", "q_proj", "kernel"), "wk": ("attn", "k_proj", "kernel"),
    "wv": ("attn", "v_proj", "kernel"), "wo": ("attn", "o_proj", "kernel"),
    "q_norm": ("attn", "q_norm", "scale"), "k_norm": ("attn", "k_norm", "scale"),
}
LINEAR = {
    "wq": ("linear_attn", "q_proj", "kernel"), "wk": ("linear_attn", "k_proj", "kernel"),
    "wv": ("linear_attn", "v_proj", "kernel"), "wg": ("linear_attn", "g_proj", "kernel"),
    "wo": ("linear_attn", "o_proj", "kernel"), "wa": ("linear_attn", "a_proj", "kernel"),
    "wb": ("linear_attn", "b_proj", "kernel"), "conv": ("linear_attn", "conv"),
    "A_log": ("linear_attn", "A_log"), "dt_bias": ("linear_attn", "dt_bias"),
    "norm": ("linear_attn", "norm"),
}


def _at(node, path):
    for k in path:
        node = node[k]
    return node


def reference_parts(variables, device=None):
    """(embedding, layer iterator, final norm, output matrix) in the plain
    reference's own names, each layer moved to `device` only when asked
    for."""
    p = variables["params"] if "params" in variables else variables
    put = (lambda a: jax.device_put(a, device)) if device is not None else (lambda a: a)
    n = sum(1 for k in p if k.startswith("layers_"))

    def layers():
        for i in range(n):
            blk = p[f"layers_{i}"]
            names = dict(SHARED, **(LINEAR if "linear_attn" in blk else FULL))
            yield {ours: put(_at(blk, path)) for ours, path in names.items()}

    return (
        put(p["tok_embed"]["embedding"]), layers(),
        put(p["final_norm"]["scale"]), put(p["lm_head"]["kernel"]),
    )


# --- counts from shapes -----------------------------------------------------

def linear_sizes(config: dict) -> tuple:
    """(heads, key width, value width, conv taps) of a linear layer."""
    return (config["linear_num_value_heads"], config["linear_key_head_dim"],
            config["linear_value_head_dim"], config["linear_conv_kernel_dim"])


def layer_params(config: dict, i: int, matmuls_only: bool = False) -> int:
    """Parameters of layer i: its mixer, its SwiGLU and its two norms; with
    `matmuls_only` those a token is multiplied by in a matrix product."""
    d = config["hidden_size"]
    mlp = 3 * d * config["intermediate_size"]
    if config["layer_types"][i] == "linear_attention":
        h, dk, dv, taps = linear_sizes(config)
        mixer = d * h * (2 * dk + 2 * dv) + h * dv * d + 2 * d * h  # q k v g, o, a b
        small = taps * h * (2 * dk + dv) + 2 * h + dv  # conv, A_log dt_bias, norm
    else:
        mixer, small = 4 * d * d, 2 * d  # q k v o; q_norm k_norm
    return mixer + mlp + (0 if matmuls_only else small + 2 * d)


def param_count(config: dict) -> int:
    """Every parameter: the layers, the final norm, the embedding and the
    untied head."""
    d = config["hidden_size"]
    return (sum(layer_params(config, i) for i in range(config["num_hidden_layers"]))
            + d + 2 * config["vocab_size"] * d)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs of one training token, forward plus backward: the matrix
    products of every layer and the head, the keys a full layer attends
    (half the sequence in the mean) and a linear layer's recurrence (per
    head S^T k, the rank-one update and S^T q: 6 dk dv)."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    h, dk, dv, _ = linear_sizes(config)
    matmuls = sum(layer_params(config, i, matmuls_only=True) for i in range(n))
    matmuls += d * config["vocab_size"]
    mixing = 0.0
    for kind in config["layer_types"]:
        mixing += 6.0 * h * dk * dv if kind == "linear_attention" else 4.0 * d * (seq + 1) / 2.0
    return 3.0 * (2.0 * matmuls + mixing)


def recurrence_decode_call(config: dict, rows: int, state_itemsize: int = 4,
                           itemsize: int = 2) -> dict:
    """What the linear layers' recurrence of ONE decode step has to do, over
    all linear layers, for `rows` live rows (a parked row's state block is
    neither read nor written). Bytes a row and layer: its state read and
    written once (float32), its conv tail read and written, and its q, k,
    v, gate and beta in and its output out (activations). FLOPs a row and
    layer: 4 dk dv a head (S^T k, the decay and the rank-one update, S^T q,
    a multiply and an add each counted once) and the two L2 norms."""
    h, dk, dv, taps = linear_sizes(config)
    layers = sum(kind == "linear_attention" for kind in config["layer_types"])
    state = 2 * h * dk * dv * state_itemsize
    tail = 2 * (taps - 1) * h * (2 * dk + dv) * itemsize
    vectors = (h * (2 * dk + dv) + 2 * h + h * dv) * itemsize
    return {
        "bytes": float(rows * layers * (state + tail + vectors)),
        "flops": float(rows * layers * (4 * h * dk * dv + 6 * h * dk)),
    }
