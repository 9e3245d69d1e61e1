"""Plain float32 reference of the published decoder block, in `jax.numpy`:
RMSNorm -> grouped-query attention with rotary embeddings -> residual ->
RMSNorm -> SwiGLU -> residual; final RMSNorm and an untied output head.
No kernels, no cache, no batching, nothing imported from the program.

Departures from the published description, each noted:
* RoPE rotates interleaved (even, odd) pairs of a head, as the model's own
  reference code (mistral-inference) does. The Hugging Face port rotates
  halves of permuted q/k projections: the same function of differently
  stored weights. Seeded random weights make the storage order immaterial.
* Attention runs in blocks of query positions so that the float32 scores of
  a 4096-token sequence (2 GiB for 32 heads) never exist at once.

A layer's weights arrive as a dict of arrays in any dtype and are upcast
here, so a caller can hand over one layer at a time and the float32 copy of
the whole model never exists. On a TPU a float32 matmul runs in lower
precision unless `highest` is set, so every function sets it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x: (L, heads, head_dim); position i rotates pair j by i / theta^(2j/D)."""
    L, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


def attention(q, k, v):
    """Causal softmax attention. q: (L, H, D); k, v: (L, KV, D), each KV
    head shared by H // KV consecutive query heads."""
    L, H, D = q.shape
    KV = k.shape[1]
    q = q.reshape(L, KV, H // KV, D)
    outs = []
    for start in range(0, L, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        s = jnp.einsum("lkrd,mkd->krlm", qb, k) / (D ** 0.5)
        rows = start + jnp.arange(qb.shape[0])
        mask = jnp.arange(L)[None, :] <= rows[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("krlm,mkd->lkrd", p, v))
    return jnp.concatenate(outs, axis=0).reshape(L, H * D)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta"))
def layer(x, w, *, heads, kv_heads, eps, theta):
    """One decoder block on x: (L, d) float32. `w` holds wq, wk, wv, wo,
    w_gate, w_up, w_down as (in, out) matrices and attn_norm, mlp_norm."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
        L = x.shape[0]
        h = rms_norm(x, w["attn_norm"], eps)
        q = (h @ w["wq"]).reshape(L, heads, -1)
        k = (h @ w["wk"]).reshape(L, kv_heads, -1)
        v = (h @ w["wv"]).reshape(L, kv_heads, -1)
        a = attention(rope(q, theta), rope(k, theta), v)
        x = x + a @ w["wo"]
        h = rms_norm(x, w["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, w_out, *, eps):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        return x @ w_out.astype(jnp.float32)


def logits(tokens, embedding, layers, final_norm, w_out, cfg, last=None):
    """Float32 logits of the last `last` positions of one sequence.

    `layers` is an iterable that yields one layer's weight dict at a time;
    `cfg` is the configuration file (Hugging Face key names)."""
    x = jnp.take(embedding, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for w in layers:
        x = layer(
            x, w, heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
        )
    if last is not None:
        x = x[-last:]
    return head(x, final_norm, w_out, eps=cfg["rms_norm_eps"])
