"""Plain float32 reference of a decoder with multi-head latent attention,
sandwich norms, leading dense layers and a sigmoid-routed sparse MLP with a
shared expert, in `jax.numpy`, read from the configuration's own (Hugging
Face) keys. The NON-absorbed equations: every head's keys and values are
up-projected from the latent and attended as keys and values. No kernels,
no cache, no batching, no sorting, nothing imported from the program.

The layer, in the order it is computed (x: tokens x hidden; every RMSNorm
has its own scale, eps `rms_norm_eps`):
1. a = N1(x). c_q = RMSNorm(a W_qa) (`q_lora_rank`); q = c_q W_qb, a head
   [q_nope (`qk_nope_head_dim`); q_rope (`qk_rope_head_dim`)];
   q_rope = RoPE(q_rope).
2. [c_kv (`kv_lora_rank`); k_r] = a W_kva; c_kv = RMSNorm(c_kv);
   k_rope = RoPE(k_r), ONE for all heads.
3. a head: k_nope = c_kv W_uk, v = c_kv W_uv, W_kvb = [W_uk; W_uv];
   s(i, j) = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) / sqrt(nope + rope),
   causal softmax over j, o_i = sum_j p(i, j) v_j.
4. x = x + N2(concat(o) W_o).
5. b = N3(x); layers before `first_k_dense_replace`: y = SwiGLU(b) at
   `intermediate_size`; the others: s = sigmoid(b W_r) in float32 over all
   experts, the `num_experts_per_tok` largest, w_e = `routed_scaling_factor`
   s_e / (their sum + 1e-20), y = sum_e w_e SwiGLU_e(b) + SwiGLU_shared(b),
   weights on expert OUTPUTS, the shared expert unweighted.
6. x = x + N4(y).
Final RMSNorm, untied head, float32 logits.

What the configuration does not say, and this file therefore assumes (the
same words stand in the configuration's `assumed`):
(1) the router: the config names no scoring_func, n_group or topk_method, so
    the family's sigmoid of each expert's logit in float32, a plain top 8 of
    the 256 with no groups and no correction bias, the 8 scores normalised to
    sum to 1 and then times routed_scaling_factor;
(2) sandwich_norm: true is the four norms above and no other;
(3) rope rotates ADJACENT pairs (value 2j with value 2j + 1) of the rotary
    values of a query head and of the one shared key;
(4) the softmax scale is (nope + rope)^-0.5 with no mscale (no YaRN in the
    config);
(5) c_kv is cached after its norm and k_rope after its rotation (which a
    reference without a cache only shows in `kv_dtype` and `fault`).

The experts held are a contiguous share (`first_expert`, as many as the
layer dicts hold): the router scores all of them and what the absent ones
would add is left out, here as in the program.

Sized to run beside 14.6 GB of the system under test: heads and query
positions in blocks, one matrix upcast to float32 at a time (the largest,
a dense layer's gate, is 566 MB), the experts one at a time, the head in
blocks of the vocabulary. A layer's weights arrive as a dict of arrays in any
dtype. On a TPU a float32 matmul runs in lower precision unless `highest` is
set, so every product sets it.

Top-k is discontinuous: a system in bfloat16 picks another 8th expert than
this file wherever two router scores lie within its rounding. Where the
configuration asks for it (`model.check.routing: "system"`) and the layers
it is handed can say which experts the system ran (`layers.system_routing`,
the glue's), a sparse layer takes a token's experts from the system IF its
own scores cannot tell them from its own choice: each lies within
`tie_margin` (a sigmoid score) of this file's last chosen one. A token
whose told experts do not is routed by this file alone, and so is every
token that was told nothing (-1). The weights are always this file's
scores. `logits` says on standard error how many choices were told,
differed and were refused.

For showing that the comparison's limits catch a fault: `kv_dtype` rounds
what a latent cache would hold (c_kv after its norm, k_rope after its
rotation) to a lower precision, `expert_dtype` the expert products'
operands; `fault` plants one of "k_rope_unrotated" (the shared key enters
the scores as projected) and "c_kv_unnormed" (the latent is up-projected
without its norm). The reference proper leaves all three None.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
HEAD_BLOCK = 8
ROW_BLOCK = 1024
VOCAB_BLOCK = 16384
HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


@jax.jit
def mm(x, w):
    """x @ w with w upcast here: one float32 copy of one matrix at a time."""
    return jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)


def rope(x, theta: float):
    """x: (L, ..., r). Position i turns value 2j against value 2j + 1 by
    i * theta^(-2j / r)."""
    L, r = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (L,) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("nope", "theta"))
def heads_attention(c_q, c_kv, k_rope, w_qb, w_kvb, *, nope, theta):
    """Causal attention of one block of heads. c_q: (L, q_rank); c_kv:
    (L, r); k_rope: (L, rope), rotated; w_qb: (q_rank, heads, nope + rope);
    w_kvb: (r, heads, nope + v). Returns (L, heads, v)."""
    L = c_q.shape[0]
    width = w_qb.shape[-1]
    q = jnp.einsum("lq,qhd->lhd", c_q, w_qb.astype(jnp.float32), precision=HIGHEST)
    kv = jnp.einsum("lr,rhd->lhd", c_kv, w_kvb.astype(jnp.float32), precision=HIGHEST)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], theta)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    outs = []
    for start in range(0, L, QUERY_BLOCK):
        qn, qr = q_nope[start:start + QUERY_BLOCK], q_rope[start:start + QUERY_BLOCK]
        s = (jnp.einsum("lhd,mhd->hlm", qn, k_nope, precision=HIGHEST)
             + jnp.einsum("lhd,md->hlm", qr, k_rope, precision=HIGHEST)) / (width ** 0.5)
        rows = (start + jnp.arange(qn.shape[0]))[:, None]
        s = jnp.where((jnp.arange(L)[None, :] <= rows)[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hlm,mhd->lhd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def attention(a, w, cfg: dict, kv_dtype=None, fault=None):
    """Steps 1-3 on a = N1(x): (L, hidden) -> (L, heads * v)."""
    L = a.shape[0]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    c_q = rms_norm(mm(a, w["w_qa"]), w["q_a_norm"], eps)
    kv = mm(a, w["w_kva"])
    c_kv, k_r = kv[:, :r], kv[:, r:]
    if fault != "c_kv_unnormed":
        c_kv = rms_norm(c_kv, w["kv_a_norm"], eps)
    k_rope = k_r if fault == "k_rope_unrotated" else rope(k_r, theta)
    if kv_dtype is not None:  # as a latent cache of that precision would hold them
        c_kv, k_rope = (t.astype(kv_dtype).astype(jnp.float32) for t in (c_kv, k_rope))
    w_qb = w["w_qb"].reshape(-1, H, nope + rot)
    w_kvb = w["w_kvb"].reshape(r, H, nope + dv)
    outs = [
        heads_attention(c_q, c_kv, k_rope, w_qb[:, h:h + HEAD_BLOCK],
                        w_kvb[:, h:h + HEAD_BLOCK], nope=nope, theta=theta)
        for h in range(0, H, HEAD_BLOCK)
    ]
    return jnp.concatenate(outs, axis=1).reshape(L, H * dv)


def swiglu(x, w_gate, w_up, w_down, cast=lambda a: a):
    """In blocks of rows (a dense layer's hidden products of 4000 tokens
    are 0.3 GB each). `cast` rounds the products' operands (the identity
    in the reference proper)."""
    x = cast(x)
    return jnp.concatenate([
        mm(cast(jax.nn.silu(mm(rows, cast(w_gate))) * mm(rows, cast(w_up))), cast(w_down))
        for rows in (x[i:i + ROW_BLOCK] for i in range(0, x.shape[0], ROW_BLOCK))
    ], axis=0)


def sparse_mlp(b, w, *, top_k, scale, first_expert, expert_dtype=None, routing=None,
               tie_margin=None):
    """b: (L, hidden). Returns (the held experts' part of the weighted sum
    plus the shared expert, a dict of the (L, top_k) experts used, the (L,)
    margin between the last chosen and the first unchosen score, and which
    tokens' told experts differed / were refused). `routing` ((L, top_k)
    experts, -1 for none) takes the place of the router's own choice for the
    tokens where every told expert's score lies within `tie_margin` of the
    last chosen one's (None: for every token told); the weights are still
    this router's scores of them."""
    scores = jax.nn.sigmoid(mm(b, w["router"]))
    top_s, top_e = jax.lax.top_k(scores, top_k + 1)
    margin = top_s[:, top_k - 1] - top_s[:, top_k]
    top_s, top_e = top_s[:, :top_k], top_e[:, :top_k]
    differs = refused = jnp.zeros(b.shape[0], bool)
    if routing is not None:
        told = (routing >= 0).all(axis=-1)
        told_e = jnp.where(told[:, None], routing, top_e)
        told_s = jnp.take_along_axis(scores, told_e, axis=-1)
        sorted_e = jnp.sort(told_e, axis=-1)
        take = told & (sorted_e[:, 1:] != sorted_e[:, :-1]).all(axis=-1)
        if tie_margin is not None:
            take &= (told_s >= top_s[:, -1:] - tie_margin).all(axis=-1)
        differs = told & (sorted_e != jnp.sort(top_e, axis=-1)).any(axis=-1)
        refused = told & ~take
        top_e = jnp.where(take[:, None], told_e, top_e)
        top_s = jnp.where(take[:, None], told_s, top_s)
    weight = scale * top_s / (top_s.sum(axis=-1, keepdims=True) + 1e-20)
    held = w["experts_gate"].shape[0]
    # (L, held): the weight a token gives each expert held here, 0 elsewhere
    per_expert = jnp.zeros((b.shape[0], held + 1), jnp.float32).at[
        jnp.arange(b.shape[0])[:, None],
        jnp.where((top_e >= first_expert) & (top_e < first_expert + held),
                  top_e - first_expert, held),
    ].add(weight)[:, :held]

    def cast(a):
        a = a.astype(jnp.float32)
        return a if expert_dtype is None else a.astype(expert_dtype).astype(jnp.float32)

    y = swiglu(b, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in range(held):
        y = y + per_expert[:, e, None] * swiglu(
            b, w["experts_gate"][e], w["experts_up"][e], w["experts_down"][e], cast)
    return y, {"chosen": top_e, "margin": margin, "differs": differs, "refused": refused}


def layer(x, w, cfg: dict, i: int, *, first_expert=0, expert_dtype=None, kv_dtype=None,
          fault=None, routing=None, tie_margin=None):
    """One block on x: (L, hidden) float32; `w` as `glue/latent_moe.py`
    fills it. Returns (x, what `sparse_mlp` says of its routing or None)."""
    eps = cfg["rms_norm_eps"]
    o = attention(rms_norm(x, w["attn_norm"], eps), w, cfg, kv_dtype, fault)
    x = x + rms_norm(mm(o, w["w_o"]), w["attn_post_norm"], eps)
    b = rms_norm(x, w["mlp_norm"], eps)
    routed = None
    if i < cfg["first_k_dense_replace"]:
        y = swiglu(b, w["w_gate"], w["w_up"], w["w_down"])
    else:
        y, routed = sparse_mlp(
            b, w, top_k=cfg["num_experts_per_tok"], scale=cfg["routed_scaling_factor"],
            first_expert=first_expert, expert_dtype=expert_dtype, routing=routing,
            tie_margin=tie_margin)
    return x + rms_norm(y, w["mlp_post_norm"], eps), routed


def head(x, final_norm, w_out, eps):
    """Final norm, then the untied head in blocks of the vocabulary."""
    x = rms_norm(x, final_norm, eps)
    return jnp.concatenate(
        [mm(x, w_out[:, v:v + VOCAB_BLOCK]) for v in range(0, w_out.shape[1], VOCAB_BLOCK)],
        axis=1)


def logits(tokens, embedding, layers, final_norm, w_out, cfg, last=None,
           record=None, expert_dtype=None, kv_dtype=None, fault=None, routing=None,
           tie_margin=None, first_expert=0):
    """Float32 logits of the last `last` positions of one sequence.

    `layers` is an iterable that yields one layer's weight dict at a time;
    `cfg` is the configuration file (Hugging Face key names). `first_expert`
    is the first of the contiguous experts the dicts hold. `routing` maps a
    sparse layer's index to the (L, top_k) experts told for it (see
    `sparse_mlp`); left None, it is the system's where
    `cfg["model"]["check"]` asks for that and `layers` can say, with the
    configuration's `tie_margin`."""
    check = cfg.get("model", {}).get("check") or {}
    asked = routing is None and check.get("routing") == "system"
    if asked and hasattr(layers, "system_routing"):
        routing, tie_margin = layers.system_routing(tokens, cfg), check["tie_margin"]
    x = jnp.take(embedding, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    told = differs = refused = 0
    for i, w in enumerate(layers):
        given = (routing or {}).get(i)
        x, routed = layer(
            x, w, cfg, i, first_expert=first_expert, expert_dtype=expert_dtype,
            kv_dtype=kv_dtype, fault=fault,
            routing=None if given is None else jnp.asarray(given), tie_margin=tie_margin)
        if routed is None:
            continue
        if record is not None:
            record.append(dict(routed, layer=i))
        if given is not None:
            told += int((jnp.asarray(given) >= 0).all(axis=-1).sum())
            differs += int(routed["differs"].sum())
            refused += int(routed["refused"].sum())
    if told:
        print(
            f"reference: of {told} (token, sparse layer) choices told by the system "
            f"{differs} differ from this file's own and {refused} were refused "
            f"(tie margin {tie_margin})", file=sys.stderr, flush=True)
    if last is not None:
        x = x[-last:]
    return head(x, final_norm, w_out, cfg["rms_norm_eps"])
