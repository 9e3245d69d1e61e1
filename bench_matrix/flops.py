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


def paged_decode_call(keys, heads: int, kv_heads: int, head_dim: int,
                      itemsize: int = 2, distinct: int = None) -> dict:
    """What one paged decode attention call (one layer of one engine step)
    has to do. `keys` holds, for every row that DECODES in the step, the
    keys it attends: its cached tokens and the one the step writes. A parked
    or mid-prefill row is not in `keys` and costs nothing. Bytes are the
    live K and V of the step's DISTINCT keys read once (`distinct_keys`: a
    block that several rows' tables hold counts once, whether or not the
    kernel reads it so; None: no two rows hold one block, every row's keys
    are its own), `kv_heads` heads (the query heads of a group share them);
    the one query and output vector a row are under 1 % of that from 64 keys
    on and left out. FLOPs: QK^T and PV for every query head of every (row,
    key) pair. Pages are not rounded up: a partly filled page is the
    kernel's cost, not the algorithm's need."""
    n = float(sum(keys))
    return {
        "bytes": (n if distinct is None else float(distinct)) * kv_heads * head_dim * 2 * itemsize,
        "flops": 4.0 * n * heads * head_dim,
    }


def distinct_keys(tables, keys, block_size: int) -> int:
    """The keys a decode step attends, a key that several rows attend
    counted once: `tables` (rows, blocks a row) holds the pool block behind
    each logical block of the rows that decode, `keys` (rows,) the keys each
    attends (from its first). Two rows attend the same key where their
    tables hold the same block: a block counts once, at the most keys any
    row attends in it. Counted from the tables alone, so it is the same
    whether or not a kernel reads a shared block once."""
    import numpy as np

    tables, keys = np.asarray(tables), np.asarray(keys, np.int64)
    if not keys.size:
        return 0
    first = np.arange(tables.shape[1], dtype=np.int64) * block_size
    held = np.clip(keys[:, None] - first[None, :], 0, block_size)  # keys of a row in a block
    most = np.zeros(int(tables.max()) + 1, np.int64)
    np.maximum.at(most, tables[held > 0], held[held > 0])
    return int(most.sum())


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least time a chip with `peaks` could take, and which bound."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(t_flops, t_bytes),
        "bound": "compute" if t_flops >= t_bytes else "memory",
    }
