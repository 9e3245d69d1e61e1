"""Plain float32 reference of a decoder that mixes linear-attention layers
(Gated DeltaNet: Yang, Kautz, Hatamizadeh, arXiv:2412.06464) with full
softmax attention, in `jax.numpy`, read from the configuration's own
(Hugging Face) keys: `layer_types` says which layer is which,
`linear_num_key_heads` / `linear_num_value_heads` (equal here),
`linear_key_head_dim`, `linear_value_head_dim`, `linear_conv_kernel_dim`
and `linear_allow_neg_eigval` size the linear mixer. No kernels, no cache,
no batching, no chunking of the recurrence, nothing imported from the
program.

A linear layer, per token t (x_t: hidden; H heads of key width dk and
value width dv):
1. q~, k~, v~ = W_q x_t, W_k x_t, W_v x_t.
2. q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~)): a depthwise
   causal conv over time with `linear_conv_kernel_dim` taps and no bias,
   conv(u)_t = sum_i c_i u_{t - taps + 1 + i}, u before the sequence 0.
3. per head: q <- q / |q| * dk^-1/2, k <- k / |k|.
4. beta_t = 2 sigmoid(W_b x_t) (the 2 is `linear_allow_neg_eigval`; 1
   without); g_t = -exp(A_log) softplus(W_a x_t + dt_bias), alpha_t =
   exp(g_t).
5. S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T, S a
   (dk x dv) matrix a head, S_0 = 0; o_t = S_t^T q_t. Here: one `lax.scan`
   over the tokens, one token a step.
6. y_t = W_o [RMSNorm_dv(o_t) w_norm * silu(W_g x_t)].
A full layer: q = RMSNorm(W_q x), k = RMSNorm(W_k x) over the whole
projection, causal softmax attention at scale head_dim^-1/2 of H heads on
`num_key_value_heads` KV heads, W_o.
The block: h = x + RMSNorm(mixer(x)); y = h + RMSNorm(SwiGLU(h)). Final
RMSNorm, untied head, float32 logits.

What the configuration does not say, and this file therefore assumes (the
same four items stand in the configuration's `assumed`):
(1) each sublayer's OUTPUT is RMS-normed before the residual add and
    nothing norms its input (the Olmo-3 family's placement);
(2) q and k of a full-attention layer are RMS-normed over the whole
    projection, before the heads are split (the family's q/k norm);
(3) `rope_parameters.rope_theta: null` means NO rotary embedding in the
    full-attention layers (position reaches them through the recurrent
    layers); given a number instead, q and k are rotated by it, value j of
    a head against value j + head_dim/2;
(4) the recurrent state is float32 (`state_dtype` below stores it in
    another precision after every token, to show that the comparison's
    limits catch that), the conv's inputs are whatever the activations are.

Attention runs in blocks of query positions and the head in blocks of the
vocabulary, each block of weights upcast when it is used, so that 2056
tokens of a model whose weights are 8 GB in bfloat16 fit beside the system
under test. A layer's weights arrive as a dict of arrays in any dtype. On a
TPU a float32 matmul runs in lower precision unless `highest` is set, so
every function sets it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
VOCAB_BLOCK = 12544


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def causal_conv(u, taps_weight):
    """u: (L, width); taps_weight: (taps, width). conv(u)_t = sum_i c_i
    u_{t - taps + 1 + i}, with zeros before the sequence: written out tap
    by tap."""
    taps, L = taps_weight.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u], axis=0)
    out = jnp.zeros_like(u)
    for i in range(taps):
        out = out + taps_weight[i] * padded[i:i + L]
    return out


def delta_rule(q, k, v, alpha, beta, state_dtype=None):
    """q, k: (L, H, dk); v: (L, H, dv); alpha, beta: (L, H). The recurrence
    of step 5, a token a step of a `lax.scan`; returns o (L, H, dv)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[:, None, None] * S
        predicted = jnp.einsum("hkv,hk->hv", S, k_t)  # alpha_t S_{t-1}^T k_t
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - predicted)[:, None, :]
        if state_dtype is not None:
            # as a state kept in that precision (an explicit rounding: the
            # compiler may drop a cast there and back as excess precision)
            info = jnp.finfo(state_dtype)
            S = jax.lax.reduce_precision(S, info.nexp, info.nmant)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))
    return o


def unit(a):
    """Step 3's norm: each head's vector over its length."""
    return a / jnp.linalg.norm(a, axis=-1, keepdims=True)


def linear_mixer(x, w, *, heads, key_dim, value_dim, neg_eigval, eps,
                 state_dtype=None):
    """Steps 1-6 on x: (L, hidden)."""
    f32 = lambda name: w[name].astype(jnp.float32)
    L = x.shape[0]
    conv = f32("conv")  # (taps, 2 H dk + H dv): q's, k's, v's channels
    nq = heads * key_dim
    q = jax.nn.silu(causal_conv(x @ f32("wq"), conv[:, :nq]))
    k = jax.nn.silu(causal_conv(x @ f32("wk"), conv[:, nq:2 * nq]))
    v = jax.nn.silu(causal_conv(x @ f32("wv"), conv[:, 2 * nq:]))
    q = q.reshape(L, heads, key_dim)
    k = k.reshape(L, heads, key_dim)
    v = v.reshape(L, heads, value_dim)
    q, k = unit(q) * key_dim ** -0.5, unit(k)
    beta = jax.nn.sigmoid(x @ f32("wb")) * (2.0 if neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(f32("A_log")) * jax.nn.softplus(x @ f32("wa") + f32("dt_bias")))
    o = delta_rule(q, k, v, alpha, beta, state_dtype)
    gate = jax.nn.silu(x @ f32("wg")).reshape(L, heads, value_dim)
    o = rms_norm(o, f32("norm"), eps) * gate
    return o.reshape(L, heads * value_dim) @ f32("wo")


def rotate_halves(x, theta):
    """x: (L, heads, D); position i turns value j against value j + D/2."""
    L, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def full_mixer(x, w, *, heads, kv_heads, eps, theta):
    """Causal softmax attention with q and k normed over the whole
    projection; x: (L, hidden)."""
    f32 = lambda name: w[name].astype(jnp.float32)
    L = x.shape[0]
    q = rms_norm(x @ f32("wq"), f32("q_norm"), eps).reshape(L, heads, -1)
    k = rms_norm(x @ f32("wk"), f32("k_norm"), eps).reshape(L, kv_heads, -1)
    v = (x @ f32("wv")).reshape(L, kv_heads, -1)
    if theta is not None:
        q, k = rotate_halves(q, theta), rotate_halves(k, theta)
    D = q.shape[-1]
    q = q.reshape(L, kv_heads, heads // kv_heads, D)
    outs = []
    for start in range(0, L, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        s = jnp.einsum("lkrd,mkd->krlm", qb, k) / (D ** 0.5)
        rows = (start + jnp.arange(qb.shape[0]))[:, None]
        s = jnp.where((jnp.arange(L)[None, :] <= rows)[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("krlm,mkd->lkrd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=0).reshape(L, heads * D) @ f32("wo")


@functools.partial(
    jax.jit,
    static_argnames=("linear", "heads", "kv_heads", "eps", "theta", "linear_heads",
                     "key_dim", "value_dim", "neg_eigval", "state_dtype"))
def layer(x, w, *, linear, heads, kv_heads, eps, theta, linear_heads, key_dim,
          value_dim, neg_eigval, state_dtype=None):
    """One block on x: (L, hidden) float32; `w` as `glue/hybrid_linear.py`
    fills it."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda name: w[name].astype(jnp.float32)
        if linear:
            mixed = linear_mixer(
                x, w, heads=linear_heads, key_dim=key_dim, value_dim=value_dim,
                neg_eigval=neg_eigval, eps=eps, state_dtype=state_dtype)
        else:
            mixed = full_mixer(x, w, heads=heads, kv_heads=kv_heads, eps=eps, theta=theta)
        h = x + rms_norm(mixed, f32("attn_norm"), eps)
        mlp = (jax.nn.silu(h @ f32("w_gate")) * (h @ f32("w_up"))) @ f32("w_down")
        return h + rms_norm(mlp, f32("mlp_norm"), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, final_norm, *, eps):
    return rms_norm(x, final_norm.astype(jnp.float32), eps)


@jax.jit
def _head_block(x, w_block):
    with jax.default_matmul_precision("highest"):
        return x @ w_block.astype(jnp.float32)


def head(x, final_norm, w_out, *, eps):
    """Final norm and the untied head, a block of the vocabulary at a time."""
    x = _normed(x, final_norm, eps=eps)
    return jnp.concatenate([
        _head_block(x, w_out[:, s:s + VOCAB_BLOCK])
        for s in range(0, w_out.shape[1], VOCAB_BLOCK)
    ], axis=1)


def layer_settings(cfg: dict, i: int) -> dict:
    """Layer i's static settings, from the configuration's keys."""
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("key and value head counts differ: not carried here")
    return dict(
        linear=cfg["layer_types"][i] == "linear_attention",
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        eps=cfg["rms_norm_eps"], theta=cfg["rope_parameters"]["rope_theta"],
        linear_heads=cfg["linear_num_value_heads"], key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"],
        neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
    )


def logits(tokens, embedding, layers, final_norm, w_out, cfg, last=None,
           state_dtype=None):
    """Float32 logits of the last `last` positions of one sequence.

    `layers` is an iterable that yields one layer's weight dict at a time;
    `cfg` is the configuration file (Hugging Face key names). `state_dtype`
    rounds the recurrent state to a lower precision after every token (the
    reference proper leaves it None): the control the cell's limits are
    set against."""
    x = jnp.take(embedding, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for i, w in enumerate(layers):
        x = layer(x, w, state_dtype=state_dtype, **layer_settings(cfg, i))
    if last is not None:
        x = x[-last:]
    return head(x, final_norm, w_out, eps=cfg["rms_norm_eps"])
