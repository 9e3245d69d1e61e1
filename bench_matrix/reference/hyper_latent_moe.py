"""Plain float32 reference of a decoder whose blocks carry FOUR residual
streams mixed by Sinkhorn-normalised hyper-connections (mHC,
arXiv:2512.24880, on Hyper-Connections, arXiv:2409.19606) around multi-head
latent attention with a YaRN-scaled rope and a sigmoid-routed sparse MLP
whose router chooses with a correction bias, in `jax.numpy`, read from the
configuration's own (Hugging Face) keys. The NON-absorbed equations: every
head's keys and values are up-projected from the latent and attended as keys
and values. No kernels, no cache, no batching, no sorting, nothing imported
from the program; what `reference/latent_moe.py` already writes (RMSNorm, a
product with one matrix upcast at a time, SwiGLU in row blocks, the head in
vocabulary blocks) is imported from it unchanged.

n = `hc_mult`, C = `hidden_size`; a token's state between sublayers is
X in R^(n x C); every RMSNorm has its own scale, eps `rms_norm_eps`.
Start: X_0[i] = embedding(token), i = 0..n-1. One sublayer s (attention,
then MLP; each with its own Phi_s (nC x (2n + n^2)), three scalars a_s,
2n + n^2 offsets b_s), all float32:
    x = vec(X); rho = (mean(x^2) + rms_norm_eps)^-1/2; m = rho * (x Phi_s)
    h_pre = sigmoid(a1 m[0:n] + b[0:n]) + hc_eps
    h_post = 2 sigmoid(a2 m[n:2n] + b[n:2n])
    A = clip(a3 m[2n:] + b[2n:], clamp_min, clamp_max) as n x n; M = exp(A);
    `hc_sinkhorn_iters` times: M = M / (rowsum(M) + hc_eps), then
    M = M / (colsum(M) + hc_eps); H_res = M
    u = sum_i h_pre[i] X[i]; y = F_s(RMSNorm_s(u));
    X'[i] = h_post[i] y + sum_j H_res[i, j] X[j]
End: x_out = sum_i h_out[i] X[i], h_out = sigmoid(a_o m_o + b_o) + hc_eps,
m_o = rho * (vec(X) Phi_o), Phi_o (nC x n); final RMSNorm, untied head,
float32 logits.
F_attention: c_q = RMSNorm(a W_qa); q = c_q W_qb, a head [q_nope; q_rope];
[c_kv; k_r] = a W_kva; c_kv = RMSNorm(c_kv); q_rope, k_rope = RoPE(...), ONE
k_rope for all heads; k_nope = c_kv W_uk, v = c_kv W_uv;
s(i, j) = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) * scale, causal
softmax, o_i = sum_j p(i, j) v_j, y = concat(o) W_o. RoPE is YaRN's: inverse
frequencies blended between theta^(-2i/r) and that over `factor` by the
linear ramp between the correction dimensions of `beta_fast` and `beta_slow`
turns at `original_max_position_embeddings`; cos and sin carry
yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim), and
scale = (nope + rope)^-0.5 * yarn_mscale(factor, mscale_all_dim)^2,
yarn_mscale(f, m) = 0.1 m ln f + 1.
F_mlp: layers before `first_k_dense_replace`: SwiGLU at `intermediate_size`;
the others: s = sigmoid(h W_r) in float32 over all experts; chosen: the
`num_experts_per_tok` largest of s + beta; w_e = `routed_scaling_factor`
s_e / (sum of the chosen s + 1e-20), from s WITHOUT beta;
y = sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h), weights on expert OUTPUTS, the
shared expert unweighted.

What the configuration does not say, and this file therefore assumes (the
same words stand in the configuration's `assumed`):
(1) the streams start as four copies of the embedding and end in the learned
    sigmoid mixture above (the Hyper-Connections paper sums them; the hc_*
    key names are the DeepSeek-V4 config's, whose forward mixes);
(2) hc_eps stands where written above, and the clamp before exp;
(3) rows are normalised before columns;
(4) H_res multiplies from the left as written (the transpose is the same
    family of matrices);
(5) the maps read the un-normed streams and rho multiplies after the
    product, Phi_s, a_s, b_s float32;
(6) rope pairs adjacent values and the softmax scale carries mscale^2 (the
    lineage's);
(7) c_kv cached after its norm;
(8) seeded weights: Phi_s ~ N(0, 1/(nC)), a_s = 1, b_s = 0, beta ~ N(0, 0.05),
    so that m is O(1), H_res is far from both the identity and 1/4, and the
    bias changes a few per cent of the choices; a published initialisation
    (a_s near 0, b_s near an identity) would make every map a constant and
    the comparison blind to them.

Sized to run beside 14 GB of the system under test with a 12.6 k-token
sequence: heads and query positions in blocks, one matrix upcast to float32
at a time, the experts one at a time, the head in blocks of the vocabulary;
the four streams in float32 are 0.72 GB there, and nothing else of that size
is alive beside them (each map is one jitted function of the streams, and
the new streams are written into the old ones' buffer). On a TPU a float32 matmul runs in lower precision
unless `highest` is set, so every product sets it.

Top-k is discontinuous: where the configuration asks for it
(`model.check.routing: "system"`) and the layers it is handed can say which
experts the system ran (`layers.system_routing`, the glue's), a sparse layer
takes a token's experts from the system IF its own scores cannot tell them
from its own choice: each told expert's s + beta lies within `tie_margin` of
this file's last chosen one's. A token whose told experts do not is routed
by this file alone, and so is every token told nothing (-1). The weights are
always this file's scores. `logits` says on standard error how many choices
were told, differed and were refused.

For showing that the comparison's limits catch a fault: `kv_dtype` rounds
what a latent cache would hold (c_kv after its norm, k_rope after its
rotation), `expert_dtype` the expert products' operands, `hc_dtype` the
maps' inputs, products and the mixtures they weigh (bfloat16: maps computed
in the activations' precision); `fault` plants one of `FAULTS`. The
reference proper leaves all four None.
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp

from .latent_moe import HIGHEST, head, mm, rms_norm, swiglu

QUERY_BLOCK = 512
HEAD_BLOCK = 4
FAULTS = (
    "h_res_transposed",  # X'[i] takes sum_j H_res[j, i] X[j]
    "one_sinkhorn_iteration",  # 1 for `hc_sinkhorn_iters`
    "h_post_without_its_2",
    "collapse_is_a_sum",  # x_out = sum_i X[i]
    "bias_in_the_weight",  # w_e from s + beta
    "bias_not_in_the_choice",  # the largest of s
    "scale_without_mscale",  # (nope + rope)^-0.5 alone
    "plain_rope",  # theta^(-2i/r), no YaRN blend
)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inverse_frequencies(r: int, theta: float, scaling: dict):
    """The r / 2 inverse frequencies of YaRN: theta^(-2i/r), over `factor`
    past the correction dimension of `beta_slow` turns, untouched before
    that of `beta_fast`, a linear ramp between."""
    plain = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    original = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return r * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return plain / scaling["factor"] * ramp + plain * (1.0 - ramp)


def rope(x, inv, factor: float):
    """x: (L, ..., r). Position i turns value 2j against value 2j + 1 by
    i * inv[j]; cos and sin carry `factor`."""
    L, r = x.shape[0], x.shape[-1]
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (L,) + (1,) * (x.ndim - 2) + (r // 2,)
    cos, sin = (factor * f(ang).reshape(shape) for f in (jnp.cos, jnp.sin))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def rope_numbers(cfg: dict, fault=None):
    """(inverse frequencies, the factor on cos and sin, the softmax scale)
    of a latent layer, from `rope_theta` and `rope_scaling`."""
    r, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    s = cfg["rope_scaling"]
    if s["type"] != "yarn":
        raise ValueError(f"rope_scaling type {s['type']!r}")
    inv = yarn_inverse_frequencies(r, theta, s)
    if fault == "plain_rope":
        inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    on_tables = yarn_mscale(s["factor"], s["mscale"]) / yarn_mscale(
        s["factor"], s["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + r) ** -0.5
    if fault != "scale_without_mscale":
        scale *= yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2
    return inv, on_tables, scale


@functools.partial(jax.jit, static_argnames=("nope", "scale"))
def heads_attention(c_q, c_kv, q_table, k_rope, w_qb, w_kvb, *, nope, scale):
    """Causal attention of one block of heads. c_q: (L, q_rank); c_kv:
    (L, r); q_table: (inverse frequencies, factor) of the queries' rope;
    k_rope: (L, rope), rotated; w_qb: (q_rank, heads, nope + rope); w_kvb:
    (r, heads, nope + v). Returns (L, heads, v)."""
    L = c_q.shape[0]
    q = jnp.einsum("lq,qhd->lhd", c_q, w_qb.astype(jnp.float32), precision=HIGHEST)
    kv = jnp.einsum("lr,rhd->lhd", c_kv, w_kvb.astype(jnp.float32), precision=HIGHEST)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], *q_table)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    outs = []
    for start in range(0, L, QUERY_BLOCK):
        qn, qr = q_nope[start:start + QUERY_BLOCK], q_rope[start:start + QUERY_BLOCK]
        s = (jnp.einsum("lhd,mhd->hlm", qn, k_nope, precision=HIGHEST)
             + jnp.einsum("lhd,md->hlm", qr, k_rope, precision=HIGHEST)) * scale
        rows = (start + jnp.arange(qn.shape[0]))[:, None]
        s = jnp.where((jnp.arange(L)[None, :] <= rows)[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hlm,mhd->lhd", p, v, precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def attention(a, w, cfg: dict, kv_dtype=None, fault=None):
    """F_attention on a = RMSNorm(u): (L, hidden) -> (L, hidden)."""
    L = a.shape[0]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    inv, on_tables, scale = rope_numbers(cfg, fault)
    c_q = rms_norm(mm(a, w["w_qa"]), w["q_a_norm"], eps)
    kv = mm(a, w["w_kva"])
    c_kv = rms_norm(kv[:, :r], w["kv_a_norm"], eps)
    k_rope = rope(kv[:, r:], inv, on_tables)
    if kv_dtype is not None:  # as a latent cache of that precision would hold them
        c_kv, k_rope = (t.astype(kv_dtype).astype(jnp.float32) for t in (c_kv, k_rope))
    w_qb = w["w_qb"].reshape(-1, H, nope + rot)
    w_kvb = w["w_kvb"].reshape(r, H, nope + dv)
    outs = [
        heads_attention(c_q, c_kv, (inv, on_tables), k_rope, w_qb[:, h:h + HEAD_BLOCK],
                        w_kvb[:, h:h + HEAD_BLOCK], nope=nope, scale=scale)
        for h in range(0, H, HEAD_BLOCK)
    ]
    return mm(jnp.concatenate(outs, axis=1).reshape(L, H * dv), w["w_o"])


def sparse_mlp(b, w, *, top_k, scale, expert_dtype=None, routing=None, tie_margin=None,
               fault=None):
    """b: (L, hidden). Returns (the weighted sum of the experts plus the
    shared expert, a dict of the (L, top_k) experts used, the (L,) margin
    between the last chosen and the first unchosen s + beta, and which
    tokens' told experts differed / were refused). `routing` ((L, top_k)
    experts, -1 for none) takes the place of the router's own choice for the
    tokens where every told expert's s + beta lies within `tie_margin` of
    the last chosen one's (None: for every token told); the weights are still
    this router's scores of them."""
    scores = jax.nn.sigmoid(mm(b, w["router"]))
    beta = w["router_bias"].astype(jnp.float32)
    choose_by = scores if fault == "bias_not_in_the_choice" else scores + beta
    top_c, top_e = jax.lax.top_k(choose_by, top_k + 1)
    margin = top_c[:, top_k - 1] - top_c[:, top_k]
    top_c, top_e = top_c[:, :top_k], top_e[:, :top_k]
    differs = refused = jnp.zeros(b.shape[0], bool)
    if routing is not None:
        told = (routing >= 0).all(axis=-1)
        told_e = jnp.where(told[:, None], routing, top_e)
        told_c = jnp.take_along_axis(choose_by, told_e, axis=-1)
        sorted_e = jnp.sort(told_e, axis=-1)
        take = told & (sorted_e[:, 1:] != sorted_e[:, :-1]).all(axis=-1)
        if tie_margin is not None:
            take &= (told_c >= top_c[:, -1:] - tie_margin).all(axis=-1)
        differs = told & (sorted_e != jnp.sort(top_e, axis=-1)).any(axis=-1)
        refused = told & ~take
        top_e = jnp.where(take[:, None], told_e, top_e)
    weigh_by = scores + beta if fault == "bias_in_the_weight" else scores
    top_s = jnp.take_along_axis(weigh_by, top_e, axis=-1)
    weight = scale * top_s / (top_s.sum(axis=-1, keepdims=True) + 1e-20)
    experts = w["experts_gate"].shape[0]
    # (L, experts): the weight a token gives each expert, 0 where not chosen
    per_expert = jnp.zeros((b.shape[0], experts), jnp.float32).at[
        jnp.arange(b.shape[0])[:, None], top_e].add(weight)

    def cast(a):
        a = a.astype(jnp.float32)
        return a if expert_dtype is None else a.astype(expert_dtype).astype(jnp.float32)

    y = swiglu(b, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in range(experts):
        y = y + per_expert[:, e, None] * swiglu(
            b, w["experts_gate"][e], w["experts_up"][e], w["experts_down"][e], cast)
    return y, {"chosen": top_e, "margin": margin, "differs": differs, "refused": refused}


def _round(a, dtype):
    return a.astype(jnp.float32) if dtype is None else a.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "norm_eps", "clamp",
                                             "hc_dtype", "fault"))
def maps(X, phi, alpha, bias, *, n, iters, eps, norm_eps, clamp, hc_dtype=None, fault=None):
    """X: (L, n, C) float32 -> (u (L, C), h_post (L, n), H_res (L, n, n)) of
    one sublayer; with a Phi of n columns (the END of the streams) u is
    x_out and the other two are None."""
    L = X.shape[0]
    X = _round(X, hc_dtype)
    x = X.reshape(L, -1)
    rho = _round((jnp.mean(x * x, axis=-1, keepdims=True) + norm_eps) ** -0.5, hc_dtype)
    m = _round(rho * _round(
        jnp.dot(x, _round(phi, hc_dtype), precision=HIGHEST), hc_dtype), hc_dtype)
    alpha, bias = alpha.astype(jnp.float32), bias.astype(jnp.float32)
    h_pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + bias[:n]) + eps
    if fault == "collapse_is_a_sum" and phi.shape[1] == n:
        h_pre = jnp.ones_like(h_pre)
    u = _round((_round(h_pre, hc_dtype)[:, :, None] * X).sum(axis=1), hc_dtype)
    if phi.shape[1] == n:
        return u, None, None
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + bias[n:2 * n])
    if fault == "h_post_without_its_2":
        h_post = h_post / 2.0
    A = jnp.clip(alpha[2] * m[:, 2 * n:] + bias[2 * n:], *clamp).reshape(L, n, n)
    M = jnp.exp(A)
    for _ in range(1 if fault == "one_sinkhorn_iteration" else iters):
        M = M / (M.sum(axis=2, keepdims=True) + eps)  # rows
        M = M / (M.sum(axis=1, keepdims=True) + eps)  # columns
    if fault == "h_res_transposed":
        M = M.transpose(0, 2, 1)
    return u, _round(h_post, hc_dtype), _round(M, hc_dtype)


@functools.partial(jax.jit, static_argnames=("hc_dtype",), donate_argnums=(0,))
def write_back(X, y, h_post, h_res, hc_dtype=None):
    """X'[i] = h_post[i] y + sum_j H_res[i, j] X[j], into X's own buffer
    (donated: the old and the new streams are never both alive)."""
    X, y = _round(X, hc_dtype), _round(y, hc_dtype)
    return h_post[:, :, None] * y[:, None, :] + sum(
        h_res[:, :, j, None] * X[:, None, j, :] for j in range(X.shape[1]))


def hc_numbers(cfg: dict) -> dict:
    return {"n": cfg["hc_mult"], "iters": cfg["hc_sinkhorn_iters"], "eps": cfg["hc_eps"],
            "norm_eps": cfg["rms_norm_eps"],
            "clamp": (cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])}


def layer(X, w, cfg: dict, i: int, *, expert_dtype=None, kv_dtype=None, hc_dtype=None,
          fault=None, routing=None, tie_margin=None):
    """One block on X: (L, n, hidden) float32; `w` as
    `glue/hyper_latent_moe.py` fills it. Returns (X, what `sparse_mlp` says
    of its routing or None)."""
    eps, hc = cfg["rms_norm_eps"], dict(hc_numbers(cfg), hc_dtype=hc_dtype, fault=fault)
    u, h_post, h_res = maps(X, w["hc_attn_phi"], w["hc_attn_alpha"], w["hc_attn_bias"], **hc)
    y = attention(rms_norm(u, w["attn_norm"], eps), w, cfg, kv_dtype, fault)
    X = write_back(X, y, h_post, h_res, hc_dtype)
    u, h_post, h_res = maps(X, w["hc_mlp_phi"], w["hc_mlp_alpha"], w["hc_mlp_bias"], **hc)
    b = rms_norm(u, w["mlp_norm"], eps)
    routed = None
    if i < cfg["first_k_dense_replace"]:
        y = swiglu(b, w["w_gate"], w["w_up"], w["w_down"])
    else:
        y, routed = sparse_mlp(
            b, w, top_k=cfg["num_experts_per_tok"], scale=cfg["routed_scaling_factor"],
            expert_dtype=expert_dtype, routing=routing, tie_margin=tie_margin, fault=fault)
    return write_back(X, y, h_post, h_res, hc_dtype), routed


def logits(tokens, embedding, layers, final_norm, w_out, cfg, last=None,
           record=None, expert_dtype=None, kv_dtype=None, hc_dtype=None, fault=None,
           routing=None, tie_margin=None):
    """Float32 logits of the last `last` positions of one sequence.

    `layers` is an iterable that yields one layer's weight dict at a time
    and, asked (`layers.out`), the three arrays of the streams' end; `cfg`
    is the configuration file (Hugging Face key names). `routing` maps a
    sparse layer's index to the (L, top_k) experts told for it (see
    `sparse_mlp`); left None, it is the system's where
    `cfg["model"]["check"]` asks for that and `layers` can say, with the
    configuration's `tie_margin`."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")
    check = cfg.get("model", {}).get("check") or {}
    asked = routing is None and check.get("routing") == "system"
    if asked and hasattr(layers, "system_routing"):
        routing, tie_margin = layers.system_routing(tokens, cfg), check["tie_margin"]
    x = jnp.take(embedding, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], cfg["hc_mult"], x.shape[1]))
    told = differs = refused = 0
    for i, w in enumerate(layers):
        given = (routing or {}).get(i)
        X, routed = layer(
            X, w, cfg, i, expert_dtype=expert_dtype, kv_dtype=kv_dtype, hc_dtype=hc_dtype,
            fault=fault, routing=None if given is None else jnp.asarray(given),
            tie_margin=tie_margin)
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
        X = X[-last:]
    x, _, _ = maps(X, *layers.out, **hc_numbers(cfg), hc_dtype=hc_dtype, fault=fault)
    return head(x, final_norm, w_out, cfg["rms_norm_eps"])
