"""Plain float32 reference of a decoder with a per-layer pattern, in
`jax.numpy`, read from the configuration's own (Hugging Face) keys:
window and full attention mixed (`layer_types`, `sliding_window`) with
per-layer query-head counts (`num_attention_heads_per_layer`) over shared
KV heads, a rotary embedding per attention kind (`rope_parameters`: YaRN and
a partial rotation, or plain), a per-head sigmoid gate on the attention
output (`gating`), and a dense or a sparse MLP a layer (`mlp_layer_types`):
softmax router in float32, the `num_experts_per_tok` largest, their weights
normalised and scaled by `moe_routed_scaling_factor`, SwiGLU experts, one
shared SwiGLU expert added unweighted. No kernels, no cache, no batching,
no sorting, nothing imported from the program.

The layer, in the order it is computed (x: tokens x hidden):
1. h = RMSNorm(x); q = h Wq as H_l heads, k = h Wk, v = h Wv as KV heads.
2. rope on q and k, of the layer's kind, over the leading
   `partial_rotary_factor` share of a head.
3. causal softmax attention at scale head_dim^-0.5, each group of
   H_l / KV query heads on one KV head; a window layer's position i attends
   j with i - window < j <= i.
4. head n's output times sigmoid(h Wg)[n]; x = x + concat(heads) Wo.
5. h2 = RMSNorm(x); x = x + MLP(h2), dense or sparse as above.
Final RMSNorm, untied head, float32 logits.

What the configuration does not say, and this file therefore assumes (the
same words stand in the configuration's `assumed`): the router scores with
softmax and renormalises its top k; `gating` is one sigmoid gate a head and
token; no RMSNorm on q and k and no gate on the shared expert; rope rotates
halves (value j with value j + r/2), as the Hugging Face port does.

Attention runs in blocks of query positions and the experts one at a time,
each upcast when it is used, so that 2056 tokens of a model whose experts
are 6 GB in bfloat16 fit beside the system under test. A layer's weights
arrive as a dict of arrays in any dtype. On a TPU a float32 matmul runs in
lower precision unless `highest` is set, so every function sets it.

Top-k is discontinuous: a system in bfloat16 picks another 8th expert than
this file wherever two router scores lie within its rounding, and the logits
of such a token then differ by a whole expert. Where the configuration asks
for it (`model.check.routing: "system"`) and the layers it is handed can say which
experts the system ran (`layers.system_routing`, the glue's), a sparse layer
takes a token's experts from the system IF its own scores cannot tell them
from its own choice: each lies within `tie_margin` (a probability) of
this file's last chosen one. A token whose told experts do not is routed by
this file alone, and so is every token that was told nothing (-1). The
weights are always this file's probabilities. `logits` says on standard
error how many choices were told, differed and were refused.

`record`, where a caller passes a list, receives per sparse layer the
experts each token was given, the margin between this file's last chosen and
first unchosen probability, and which tokens' told experts differed and were
refused. `routing` hands the sparse layers choices directly (with
`tie_margin=None`: taken whatever the scores say, to tell a wrong layer from
a flipped choice). `expert_dtype` rounds the expert products' operands,
`kv_dtype` the keys and values, to a lower precision (float8, say), for
showing that the comparison's limits catch it; the reference proper leaves
both None.
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def inverse_frequencies(rope: dict, rotary: int):
    """(inverse frequencies (rotary / 2,), factor on cos and sin) of one
    entry of `rope_parameters`."""
    plain = 1.0 / (
        rope["rope_theta"] ** (jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    )
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def correction_dim(turns):
        # the dimension whose wavelength makes `turns` turns over the
        # original context
        return rotary * math.log(
            rope["original_max_position_embeddings"] / (turns * 2 * math.pi)
        ) / (2 * math.log(rope["rope_theta"]))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), rotary - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(rotary // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
    )
    # below `low` the published frequency, above `high` that over `factor`
    return plain * (1 - ramp) + plain / rope["factor"] * ramp, rope["attention_factor"]


def rope(x, rope_cfg: dict):
    """x: (L, heads, head_dim). Position i turns value j of the leading
    `partial_rotary_factor` share of a head against value j + r/2."""
    L, _, D = x.shape
    r = int(D * rope_cfg["partial_rotary_factor"])
    inv, factor = inverse_frequencies(rope_cfg, r)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    a, b, rest = x[..., : r // 2], x[..., r // 2: r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def attention(q, k, v, window=None):
    """Causal softmax attention, inside `window` keys where one is given.
    q: (L, H, D); k, v: (L, KV, D), each KV head shared by H // KV
    consecutive query heads."""
    L, H, D = q.shape
    KV = k.shape[1]
    q = q.reshape(L, KV, H // KV, D)
    outs = []
    for start in range(0, L, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        s = jnp.einsum("lkrd,mkd->krlm", qb, k) / (D ** 0.5)
        rows = (start + jnp.arange(qb.shape[0]))[:, None]
        cols = jnp.arange(L)[None, :]
        mask = cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("krlm,mkd->lkrd", p, v))
    return jnp.concatenate(outs, axis=0).reshape(L, H, D)


def swiglu(x, w_gate, w_up, w_down, cast=lambda a: a):
    """`cast` rounds the second product's left operand (the identity in the
    reference proper)."""
    return cast(jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def sparse_mlp(h, w, *, top_k, scale, first_expert, expert_dtype, routing=None,
               tie_margin=None):
    """h: (L, d). Returns (the held experts' part of the weighted sum plus
    the shared expert, a dict of the (L, top_k) experts used, the (L,)
    margin, and which tokens' told experts differed / were refused).
    `routing` ((L, top_k) experts, -1 for none) takes the place of the
    router's own choice for the tokens where every told expert's
    probability lies within `tie_margin` of the last chosen one's (None:
    for every token told); the weights are still this router's
    probabilities of them."""
    probs = jax.nn.softmax(h @ w["router"].astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k + 1)
    margin = top_p[:, top_k - 1] - top_p[:, top_k]
    top_p, top_e = top_p[:, :top_k], top_e[:, :top_k]
    differs = refused = jnp.zeros(h.shape[0], bool)
    if routing is not None:
        told = (routing >= 0).all(axis=-1)
        told_e = jnp.where(told[:, None], routing, top_e)
        told_p = jnp.take_along_axis(probs, told_e, axis=-1)
        sorted_e = jnp.sort(told_e, axis=-1)
        take = told & (sorted_e[:, 1:] != sorted_e[:, :-1]).all(axis=-1)
        if tie_margin is not None:
            take &= (told_p >= top_p[:, -1:] - tie_margin).all(axis=-1)
        differs = told & (sorted_e != jnp.sort(top_e, axis=-1)).any(axis=-1)
        refused = told & ~take
        top_e = jnp.where(take[:, None], told_e, top_e)
        top_p = jnp.where(take[:, None], told_p, top_p)
    weight = scale * top_p / top_p.sum(axis=-1, keepdims=True)
    held = w["experts_gate"].shape[0]
    # (L, held): the weight a token gives each expert held here, 0 elsewhere
    per_expert = jnp.zeros((h.shape[0], held + 1), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None],
        jnp.where((top_e >= first_expert) & (top_e < first_expert + held),
                  top_e - first_expert, held),
    ].add(weight)[:, :held]

    def cast(a):
        a = a.astype(jnp.float32)
        return a if expert_dtype is None else a.astype(expert_dtype).astype(jnp.float32)

    def one(e):
        out = swiglu(cast(h), cast(w["experts_gate"][e]), cast(w["experts_up"][e]),
                     cast(w["experts_down"][e]), cast)
        return per_expert[:, e, None] * out

    y = jax.lax.fori_loop(
        0, held, lambda e, acc: acc + one(e), jnp.zeros_like(h))
    shared = swiglu(h, *(w[n].astype(jnp.float32)
                         for n in ("shared_gate", "shared_up", "shared_down")))
    return y + shared, {"chosen": top_e, "margin": margin, "differs": differs,
                        "refused": refused}


@functools.partial(
    jax.jit,
    static_argnames=("heads", "kv_heads", "head_dim", "eps", "window", "rope_cfg",
                     "sparse", "top_k", "scale", "first_expert", "expert_dtype",
                     "kv_dtype", "tie_margin"))
def layer(x, w, *, heads, kv_heads, head_dim, eps, window, rope_cfg, sparse,
          top_k, scale, first_expert, expert_dtype=None, kv_dtype=None, routing=None,
          tie_margin=None):
    """One block on x: (L, d) float32; `w` as `glue/sparse_window.py` fills
    it. Returns (x, what `sparse_mlp` says of its routing or None)."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda name: w[name].astype(jnp.float32)
        L = x.shape[0]
        h = rms_norm(x, f32("attn_norm"), eps)
        q = (h @ f32("wq")).reshape(L, heads, head_dim)
        k = (h @ f32("wk")).reshape(L, kv_heads, head_dim)
        v = (h @ f32("wv")).reshape(L, kv_heads, head_dim)
        rope_cfg = dict(rope_cfg)
        k = rope(k, rope_cfg)
        if kv_dtype is not None:  # as a cache of that precision would hold them
            k, v = (t.astype(kv_dtype).astype(jnp.float32) for t in (k, v))
        a = attention(rope(q, rope_cfg), k, v, window)
        if "w_head_gate" in w:
            a = a * jax.nn.sigmoid(h @ f32("w_head_gate"))[:, :, None]
        x = x + a.reshape(L, heads * head_dim) @ f32("wo")
        h = rms_norm(x, f32("mlp_norm"), eps)
        if not sparse:
            return x + swiglu(h, f32("w_gate"), f32("w_up"), f32("w_down")), None
        y, routed = sparse_mlp(
            h, w, top_k=top_k, scale=scale, first_expert=first_expert,
            expert_dtype=expert_dtype, routing=routing, tie_margin=tie_margin)
        return x + y, routed


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, w_out, *, eps):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        return x @ w_out.astype(jnp.float32)


def layer_settings(cfg: dict, i: int) -> dict:
    """Layer i's static settings, from the configuration's per-layer lists."""
    kind = cfg["layer_types"][i]
    rope_cfg = dict(cfg["rope_parameters"][kind])
    rope_cfg.setdefault("partial_rotary_factor", cfg.get("partial_rotary_factor", 1.0))
    return dict(
        heads=cfg["num_attention_heads_per_layer"][i],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        eps=cfg["rms_norm_eps"],
        window=cfg["sliding_window"] if kind == "sliding_attention" else None,
        rope_cfg=tuple(sorted(rope_cfg.items())),
        sparse=cfg["mlp_layer_types"][i] == "sparse",
        top_k=cfg["num_experts_per_tok"], scale=cfg["moe_routed_scaling_factor"],
    )


def logits(tokens, embedding, layers, final_norm, w_out, cfg, last=None,
           record=None, expert_dtype=None, kv_dtype=None, routing=None,
           tie_margin=None, first_expert=0):
    """Float32 logits of the last `last` positions of one sequence.

    `layers` is an iterable that yields one layer's weight dict at a time;
    `cfg` is the configuration file (Hugging Face key names). `first_expert`
    is the first of the contiguous experts the dicts hold (all, from 0, in
    the configurations the benchmark has). `routing` maps a sparse layer's
    index to the (L, top_k) experts told for it (see `sparse_mlp`); left
    None, it is the system's where `cfg["model"]["check"]` asks for that and
    `layers` can say, with the configuration's `tie_margin`."""
    check = cfg.get("model", {}).get("check") or {}
    asked = routing is None and check.get("routing") == "system"
    if asked and hasattr(layers, "system_routing"):
        routing, tie_margin = layers.system_routing(tokens, cfg), check["tie_margin"]
    x = jnp.take(embedding, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    told = differs = refused = 0
    for i, w in enumerate(layers):
        given = (routing or {}).get(i)
        x, routed = layer(
            x, w, first_expert=first_expert, expert_dtype=expert_dtype,
            kv_dtype=kv_dtype, routing=None if given is None else jnp.asarray(given),
            tie_margin=tie_margin, **layer_settings(cfg, i))
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
    return head(x, final_norm, w_out, eps=cfg["rms_norm_eps"])
