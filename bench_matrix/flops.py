"""Operations and bytes from shapes. Keys are the configuration file's
(Hugging Face) names. Recomputed operations never count: a training step is
one forward and one backward pass (3x the forward matmul work), whatever
remat executes.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> dict:
    """Parameters that take part in a matmul for every token."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * f
    return {
        "attn": attn, "mlp": mlp, "layer": attn + mlp,
        "head": d * cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
    }


def param_count(cfg: dict) -> int:
    """Every parameter: matmuls, two norms a layer, final norm, embedding."""
    m = matmul_params(cfg)
    d = cfg["hidden_size"]
    return (
        m["layers"] * (m["layer"] + 2 * d) + d + m["head"]
        + cfg["vocab_size"] * d
    )


def attention_forward_flops_per_token(cfg: dict, seq: int, causal=True) -> float:
    """QK^T and PV of one layer for one token of a `seq`-token sequence,
    averaged over positions: 2 matmuls x 2 flops x seq keys x (heads x
    head_dim), halved under a causal mask."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4.0 * seq * width * (0.5 if causal else 1.0)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    m = matmul_params(cfg)
    return (
        2.0 * (m["layers"] * m["layer"] + m["head"])
        + m["layers"] * attention_forward_flops_per_token(cfg, seq)
    )


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one training token: forward plus backward."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def flash_call(batch: int, seq: int, heads: int, head_dim: int,
               causal: bool = True, itemsize: int = 2) -> dict:
    """What one flash-attention call has to do, forward and backward, for
    (batch, seq, heads, head_dim) q/k/v of `itemsize` bytes (the model
    repeats K/V to all heads before the kernel, so the kernel sees `heads`
    K/V heads). Forward: QK^T and PV. Backward: the score recompute, dP,
    dQ, dK and dV - five matmuls of the same size. Bytes are the operands
    read once and the results written once (the float32 row statistics are
    under 1 % at head_dim 128 and left out)."""
    half = 0.5 if causal else 1.0
    mm = 2.0 * batch * heads * seq * seq * head_dim * half
    tensor = batch * seq * heads * head_dim * itemsize
    return {
        "forward_flops": 2 * mm, "backward_flops": 5 * mm,
        "forward_bytes": 4 * tensor,  # q, k, v in; o out
        "backward_bytes": 8 * tensor,  # q, k, v, o, do in; dq, dk, dv out
    }


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least time a chip with `peaks` could take, and which bound."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "compute" if t_flops >= t_bytes else "memory",
    }
