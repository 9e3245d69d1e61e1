"""Plain float32 reference of a decoder that mixes gated short-convolution
layers with grouped-query softmax attention, over leading dense SwiGLU
layers and a sigmoid-routed sparse MLP with an expert bias and no shared
expert (the LFM2-MoE family), in `jax.numpy`, read from the configuration's
own (Hugging Face) keys: `layer_types` says which layer is which ("conv" /
"full_attention"), `conv_L_cache` the conv's taps, `num_dense_layers` the
leading dense layers. No kernels, no cache, no batching, no sorting, nothing
imported from the program.

One block, x: tokens x hidden, every RMSNorm with its own scale and
`norm_eps`:
    h = x + operator(RMSNorm_op(x));  y = h + ffn(RMSNorm_ffn(h))
conv operator (n = RMSNorm_op(x)):
 1. [B, C, u] = split3(n W_in)            (W_in: hidden -> 3 hidden, no bias)
 2. z_t = sum_{j < taps} w_j (B * u)_{t - (taps - 1) + j}, zeros before the
    sequence: a depthwise causal conv, `conv_L_cache` taps a channel, no
    bias, no activation
 3. out = (C * z) W_out
attention operator:
 1. q, k, v = n W_q, n W_k, n W_v; q and k RMS-normed over EACH head's
    `hidden / heads` values (one scale of that many for q, one for k)
 2. rope at `rope_theta` on all of a head's values, value j against value
    j + head/2
 3. causal softmax(q k^T / sqrt(head)) v, `num_attention_heads` query heads
    on `num_key_value_heads` KV heads; W_o
ffn, layers before `num_dense_layers`: SwiGLU at `intermediate_size`.
ffn, the others: s = sigmoid(n W_r) in float32 over all `published`
experts; chosen = the `num_experts_per_tok` largest of s + b (b the expert
bias: in the choice, in no weight); w_e = `routed_scaling_factor` s_e / (sum
of the chosen s + 1e-6); y = sum_e w_e SwiGLU_e(n) at
`moe_intermediate_size`, weights on expert OUTPUTS; no shared expert.
model: embedding -> the blocks -> RMSNorm -> logits = h E^T, the embedding
itself (a tied head: `logits` takes `w_out` and is handed the embedding).

Departures from the published forward, each also in the configuration's
`assumed`:
(a) the experts held are a contiguous share (`first_expert`, as many as the
    layer dicts hold: this chip's 16 of 32); the router scores all of them,
    the weights are normalised over all `num_experts_per_tok` chosen whether
    held or not, and what the absent experts would add is left out, here as
    in the program;
(b) where the configuration asks for it (`model.check.routing: "system"`)
    a sparse layer takes a token's experts from the system under test IF
    this file's own biased scores cannot tell them from its own choice
    (each within `tie_margin` of its own last chosen one), because top-4 of
    32 is discontinuous and a bfloat16 system flips near-ties; the weights
    are always this file's scores. `logits` says on standard error how many
    choices were told, differed and were refused;
(c) the published forward runs in bfloat16 throughout; this file is
    float32 at `highest`.

Sized to run beside 12.2 GB of the system under test: attention in blocks
of query positions, one matrix upcast to float32 at a time, the experts one
at a time, the head in blocks of the vocabulary. A layer's weights arrive
as a dict of arrays in any dtype.

For showing that the comparison's limits catch a fault (the reference
proper leaves all of these None): `mixer_dtype` rounds the conv operator's
three elementwise products and its taps' operands to a lower precision,
`router_dtype` the router's operands and scores; `fault` plants one of
"whole_norm" (q and k normed over the whole projection with the per-head
scale tiled), "untied_head" (a head of its own, seeded), "stale_tail" (at
every hand-over between calls, which is every multiple of `chunk` and, given
`prompt`, every token from the prompt's end on (a decode step is a call of
its own), the conv reads the tail one token older than it should: tokens
t - 2 and t - 3 behind a call's first token), "neighbour_tail" (there it
reads another request's tail: the inputs of 64 tokens earlier) and
"padded_tail" (behind the prompt's end, `prompt` tokens, the conv of the
decoded tokens reads the tail a padded chunk would leave: zeros).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
ROW_BLOCK = 1024
VOCAB_BLOCK = 16384
HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = (None, "whole_norm", "untied_head", "stale_tail", "neighbour_tail", "padded_tail")


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


@jax.jit
def mm(x, w):
    """x @ w with w upcast here: one float32 copy of one matrix at a time."""
    return jnp.dot(x, w.astype(jnp.float32), precision=HIGHEST)


def _rounded(dtype):
    """Values as a lower precision holds them, float32 again (None: as they are)."""
    if dtype is None:
        return lambda a: a
    return lambda a: a.astype(dtype).astype(jnp.float32)


def conv_operator(n, w, *, taps, mixer_dtype=None, fault=None, chunk=None, prompt=None):
    """Steps 1-3 of the conv operator on n = RMSNorm_op(x): (L, hidden)."""
    L, D = n.shape
    cast = _rounded(mixer_dtype)
    gate, c, u = jnp.split(cast(mm(n, w["w_in"])), 3, axis=-1)  # B, C, u
    bu = cast(gate * u)
    padded = jnp.concatenate([jnp.zeros((taps - 1, D), bu.dtype), bu], axis=0)
    # row t, tap j: the input (B * u)_{t - (taps - 1) + j}
    seen = jnp.stack([padded[j:j + L] for j in range(taps)], axis=1)  # (L, taps, D)
    if fault in ("stale_tail", "neighbour_tail", "padded_tail"):
        seen = _wrong_tail(seen, padded, fault, taps, chunk, prompt)
    taps_weight = cast(w["conv"].astype(jnp.float32))
    z = cast(jnp.einsum("ltd,td->ld", seen, taps_weight, precision=HIGHEST))
    return mm(cast(c * z), w["w_out"])


def _wrong_tail(seen, padded, fault, taps, chunk, prompt):
    """`seen` with the tail (the inputs of EARLIER calls) replaced where a
    faulty hand-over would have replaced it: for the first `taps - 1` tokens
    behind a hand-over, the taps that reach back over it."""
    L = seen.shape[0]
    t = jnp.arange(L)[:, None]
    j = jnp.arange(taps)[None, :]
    source = t - (taps - 1) + j  # the token a tap reads
    if fault == "padded_tail":
        over = (t >= prompt) & (source < prompt)
        return jnp.where(over[..., None], 0.0, seen)
    start = (t // chunk) * chunk  # the first token of the call that holds t
    if prompt is not None:  # behind the prompt every token is a call of its own
        start = jnp.where(t >= prompt, t, start)
    over = (start > 0) & (source < start)
    shift = 1 if fault == "stale_tail" else 64
    wrong = jnp.take(padded, jnp.clip(source - shift + (taps - 1), 0, None), axis=0)
    return jnp.where(over[..., None], wrong, seen)


def rotate_halves(x, theta):
    """x: (L, heads, D); position i turns value j against value j + D/2."""
    L, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_operator(n, w, *, heads, kv_heads, eps, theta, fault=None):
    """Steps 1-3 of the attention operator on n = RMSNorm_op(x): (L, hidden)."""
    L = n.shape[0]
    q, k, v = mm(n, w["wq"]), mm(n, w["wk"]), mm(n, w["wv"])
    if fault == "whole_norm":
        tile = lambda s, a: jnp.tile(s.astype(jnp.float32), a.shape[-1] // s.shape[0])
        q, k = rms_norm(q, tile(w["q_norm"], q), eps), rms_norm(k, tile(w["k_norm"], k), eps)
    q, k, v = (a.reshape(L, h, -1) for a, h in ((q, heads), (k, kv_heads), (v, kv_heads)))
    if fault != "whole_norm":
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    q, k = rotate_halves(q, theta), rotate_halves(k, theta)
    D = q.shape[-1]
    q = q.reshape(L, kv_heads, heads // kv_heads, D)
    outs = []
    for start in range(0, L, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        s = jnp.einsum("lkrd,mkd->krlm", qb, k, precision=HIGHEST) / (D ** 0.5)
        rows = (start + jnp.arange(qb.shape[0]))[:, None]
        s = jnp.where((jnp.arange(L)[None, :] <= rows)[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("krlm,mkd->lkrd", jax.nn.softmax(s, axis=-1), v,
                               precision=HIGHEST))
    return mm(jnp.concatenate(outs, axis=0).reshape(L, heads * D), w["wo"])


def swiglu(x, w_gate, w_up, w_down):
    """In blocks of rows."""
    return jnp.concatenate([
        mm(jax.nn.silu(mm(rows, w_gate)) * mm(rows, w_up), w_down)
        for rows in (x[i:i + ROW_BLOCK] for i in range(0, x.shape[0], ROW_BLOCK))
    ], axis=0)


def sparse_ffn(n, w, *, top_k, scale, first_expert, router_dtype=None, routing=None,
               tie_margin=None):
    """n: (L, hidden). Returns (the held experts' part of the weighted sum,
    a dict of the (L, top_k) experts used, the (L,) margin between the last
    chosen and the first unchosen biased score, and which tokens' told
    experts differed / were refused). `routing` ((L, top_k) experts, -1 for
    none) takes the place of this router's own choice for the tokens where
    every told expert's biased score lies within `tie_margin` of the last
    chosen one's (None: for every token told); the weights are still this
    router's scores of them, without the bias."""
    cast = _rounded(router_dtype)
    scores = cast(jax.nn.sigmoid(cast(mm(cast(n), cast(w["router"].astype(jnp.float32))))))
    biased = scores + w["router_bias"].astype(jnp.float32)
    top_b, top_e = jax.lax.top_k(biased, top_k + 1)
    margin = top_b[:, top_k - 1] - top_b[:, top_k]
    top_b, top_e = top_b[:, :top_k], top_e[:, :top_k]
    differs = refused = jnp.zeros(n.shape[0], bool)
    if routing is not None:
        told = (routing >= 0).all(axis=-1)
        told_e = jnp.where(told[:, None], routing, top_e)
        told_b = jnp.take_along_axis(biased, told_e, axis=-1)
        sorted_e = jnp.sort(told_e, axis=-1)
        take = told & (sorted_e[:, 1:] != sorted_e[:, :-1]).all(axis=-1)
        if tie_margin is not None:
            take &= (told_b >= top_b[:, -1:] - tie_margin).all(axis=-1)
        differs = told & (sorted_e != jnp.sort(top_e, axis=-1)).any(axis=-1)
        refused = told & ~take
        top_e = jnp.where(take[:, None], told_e, top_e)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    weight = scale * top_s / (top_s.sum(axis=-1, keepdims=True) + 1e-6)
    held = w["experts_gate"].shape[0]
    # (L, held): the weight a token gives each expert held here, 0 elsewhere
    per_expert = jnp.zeros((n.shape[0], held + 1), jnp.float32).at[
        jnp.arange(n.shape[0])[:, None],
        jnp.where((top_e >= first_expert) & (top_e < first_expert + held),
                  top_e - first_expert, held),
    ].add(weight)[:, :held]
    y = jnp.zeros_like(n)
    for e in range(held):
        y = y + per_expert[:, e, None] * swiglu(
            n, w["experts_gate"][e], w["experts_up"][e], w["experts_down"][e])
    return y, {"chosen": top_e, "margin": margin, "differs": differs, "refused": refused}


def layer(x, w, cfg: dict, i: int, *, first_expert=0, mixer_dtype=None, router_dtype=None,
          fault=None, chunk=None, prompt=None, routing=None, tie_margin=None):
    """One block on x: (L, hidden) float32; `w` as `glue/conv_moe.py` fills
    it. Returns (x, what `sparse_ffn` says of its routing or None)."""
    eps = cfg["norm_eps"]
    n = rms_norm(x, w["operator_norm"], eps)
    if cfg["layer_types"][i] == "conv":
        mixed = conv_operator(n, w, taps=cfg["conv_L_cache"], mixer_dtype=mixer_dtype,
                              fault=fault, chunk=chunk, prompt=prompt)
    else:
        mixed = attention_operator(
            n, w, heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
            eps=eps, theta=float(cfg["rope_theta"]), fault=fault)
    h = x + mixed
    n = rms_norm(h, w["ffn_norm"], eps)
    if i < cfg["num_dense_layers"]:
        return h + swiglu(n, w["w_gate"], w["w_up"], w["w_down"]), None
    y, routed = sparse_ffn(
        n, w, top_k=cfg["num_experts_per_tok"], scale=cfg["routed_scaling_factor"],
        first_expert=first_expert, router_dtype=router_dtype, routing=routing,
        tie_margin=tie_margin)
    return h + y, routed


def head(x, final_norm, w_out, eps):
    """Final norm, then the logits against `w_out` (hidden, vocabulary) in
    blocks of the vocabulary."""
    x = rms_norm(x, final_norm, eps)
    return jnp.concatenate(
        [mm(x, w_out[:, v:v + VOCAB_BLOCK]) for v in range(0, w_out.shape[1], VOCAB_BLOCK)],
        axis=1)


def logits(tokens, embedding, layers, final_norm, w_out, cfg, last=None, record=None,
           mixer_dtype=None, router_dtype=None, fault=None, chunk=None, prompt=None,
           routing=None, tie_margin=None, first_expert=0):
    """Float32 logits of the last `last` positions of one sequence.

    `layers` is an iterable that yields one layer's weight dict at a time;
    `cfg` is the configuration file (Hugging Face key names); `w_out` is
    (hidden, vocabulary): the glue hands over the embedding's transpose.
    `first_expert` is the first of the contiguous experts the dicts hold.
    `routing` maps a sparse layer's index to the (L, top_k) experts told for
    it (see `sparse_ffn`); left None, it is the system's where
    `cfg["model"]["check"]` asks for that and `layers` can say, with the
    configuration's `tie_margin`. `chunk` and `prompt` place the planted
    tail faults (the module's docstring)."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r}")
    check = cfg.get("model", {}).get("check") or {}
    asked = routing is None and check.get("routing") == "system"
    if asked and hasattr(layers, "system_routing"):
        routing, tie_margin = layers.system_routing(tokens, cfg), check["tie_margin"]
    x = jnp.take(embedding, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    told = differs = refused = 0
    for i, w in enumerate(layers):
        given = (routing or {}).get(i)
        x, routed = layer(
            x, w, cfg, i, first_expert=first_expert, mixer_dtype=mixer_dtype,
            router_dtype=router_dtype, fault=fault, chunk=chunk, prompt=prompt,
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
    if fault == "untied_head":
        w_out = jax.random.normal(jax.random.PRNGKey(0), w_out.shape, jnp.float32) * (
            w_out.shape[0] ** -0.5)
    return head(x, final_norm, w_out, cfg["norm_eps"])
