"""Plain float32 reference of a decoder that mixes linear-attention layers
whose decay is a VECTOR a head (Kimi Delta Attention, arXiv:2510.26692) with
NoPE grouped-query softmax attention under an elementwise output gate, every
layer's MLP a sigmoid-routed sparse one with a shared expert (the Solar
Open 2 family), in `jax.numpy`, read from the configuration's own (Hugging
Face) keys: `gqa_layers` lists the softmax layers (every other layer is
linear), `linear_attn_config` sizes the linear mixer (`num_heads`,
`head_dim` for keys and values alike, `short_conv_kernel_size`),
`kda_allow_neg_eigval` puts the 2 on beta. No kernels, no cache, no
batching, no chunking of the recurrence, nothing imported from the program.

One block, x: tokens x hidden, every RMSNorm with its own scale and
`rms_norm_eps`, n the normed input of a sublayer:
    h = x + mixer(RMSNorm(x));   y = h + moe(RMSNorm(h))
linear mixer (KDA), H heads of dk = dv = `head_dim`, state S (dk x dv) a
head, float32, zero before the sequence; per token t:
 1. q, k, v = silu(conv(n W_q)), silu(conv(n W_k)), silu(conv(n W_v)): a
    depthwise causal conv over time, `short_conv_kernel_size` taps, no bias,
    conv(u)_t = sum_i c_i u_{t - taps + 1 + i}, u before the sequence 0
 2. per head: q <- q / |q| * dk^-1/2,  k <- k / |k|
 3. g = -exp(A_log[head]) * softplus((n W_f1) W_f2 + dt_bias): a VECTOR of
    dk a head (W_f1: hidden -> rank, W_f2: rank -> H dk; `A_log` one a head,
    `dt_bias` one a channel); alpha = exp(g) in (0, 1)^dk
 4. beta = 2 sigmoid(n W_b), one a head (the 2: `kda_allow_neg_eigval`)
 5. S <- Diag(alpha) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
    (here: ONE `lax.scan` over the tokens, a token a step)
 6. mixer = [RMSNorm_dv(o) * w * sigmoid((n W_g1) W_g2)] W_o
softmax mixer (NoPE GQA): q, k, v = n W_q, n W_k, n W_v, NO rotary embedding
and no q/k norm; causal softmax(q k^T / sqrt(head_dim)) v,
`num_attention_heads` query heads on `num_key_value_heads` KV heads;
mixer = [attn * sigmoid(n W_gate)] W_o, the gate elementwise over the whole
attention output.
moe: s = sigmoid(n W_r) in float32 over all `published` experts; chosen =
the `num_experts_per_tok` largest of s + b (b the correction bias: in the
choice, in no weight); w_e = `routed_scaling_factor` s_e / (sum of the chosen
s + 1e-20); moe = sum_e w_e SwiGLU_e(n) at `moe_intermediate_size` +
SwiGLU_shared(n), the shared expert unweighted.
model: embedding -> the blocks -> RMSNorm -> logits = h W_head (untied).

Departures from the published forward, each also in the configuration's
`assumed`:
(a) the experts held are a contiguous share (`first_expert`, as many as the
    layer dicts hold: this chip's 40 of 320); the router scores all of them,
    the weights are normalised over all `num_experts_per_tok` chosen whether
    held or not, what the absent experts would add is left out and the
    shared expert is computed here, as in the program;
(b) the vocabulary is the slice the embedding and the head hold;
(c) `gqa_layers` is read below the depth and ignored past it;
(d) where the configuration asks for it (`model.check.routing: "system"`) a
    sparse layer takes a token's experts from the system under test IF this
    file's own biased scores cannot tell them from its own choice (each
    within `tie_margin` of its own last chosen one), because top-8 of 320 is
    discontinuous and a bfloat16 system flips near-ties; the weights are
    always this file's scores. `logits` says on standard error how many
    choices were told, differed and were refused;
(e) the published forward runs in bfloat16; this file is float32 at
    `highest`.

Sized to run beside 9 GB of the system under test: attention in blocks of
query positions, one matrix upcast to float32 at a time, the experts one at
a time, the head in blocks of the vocabulary. A layer's weights arrive as a
dict of arrays in any dtype.

For showing that the comparison's limits catch a fault (the reference
proper leaves all of these None): `state_dtype` rounds the recurrent state
to a lower precision after every token, `rule_dtype` what the rule is
computed from (q, k, v, the decay and beta of step 5), `router_dtype` the
router's operands and scores; `fault` plants one of "scalar_decay" (g
averaged over a head's channels: one decay a head), "beta_01" (beta without its 2), "head_gate" (the
softmax layers' gate one value a head: the mean of the head's gate logits),
"no_shared" (the shared expert left out), "stale_tail" (at every hand-over
between calls, which is every multiple of `chunk` and, given `prompt`, every
token from the prompt's end on, the conv reads the tail one token older than
it should), "neighbour_tail" (there it reads another request's tail: the
inputs of 64 tokens earlier) and "neighbour_state" (at the last hand-over
between chunks before `prompt`, the recurrence goes on from what the block
held one chunk earlier).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
ROW_BLOCK = 1024
VOCAB_BLOCK = 12288
HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = (None, "scalar_decay", "beta_01", "head_gate", "no_shared", "stale_tail",
          "neighbour_tail", "neighbour_state")


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


def causal_conv(u, taps_weight, fault=None, chunk=None, prompt=None):
    """u: (L, width); taps_weight: (taps, width). conv(u)_t = sum_i c_i
    u_{t - taps + 1 + i}, zeros before the sequence, tap by tap."""
    taps, L = taps_weight.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u], axis=0)
    # row t, tap j: the input u_{t - (taps - 1) + j}
    seen = jnp.stack([padded[j:j + L] for j in range(taps)], axis=1)  # (L, taps, width)
    if fault in ("stale_tail", "neighbour_tail"):
        seen = _wrong_tail(seen, padded, fault, taps, chunk, prompt)
    return jnp.einsum("ltd,td->ld", seen, taps_weight.astype(jnp.float32), precision=HIGHEST)


def _wrong_tail(seen, padded, fault, taps, chunk, prompt):
    """`seen` with the tail (the inputs of EARLIER calls) replaced where a
    faulty hand-over would have replaced it: for the first `taps - 1` tokens
    behind a hand-over, the taps that reach back over it."""
    L = seen.shape[0]
    t = jnp.arange(L)[:, None]
    j = jnp.arange(taps)[None, :]
    source = t - (taps - 1) + j  # the token a tap reads
    start = (t // chunk) * chunk  # the first token of the call that holds t
    if prompt is not None:  # behind the prompt every token is a call of its own
        start = jnp.where(t >= prompt, t, start)
    over = (start > 0) & (source < start)
    shift = 1 if fault == "stale_tail" else 64
    wrong = jnp.take(padded, jnp.clip(source - shift + (taps - 1), 0, None), axis=0)
    return jnp.where(over[..., None], wrong, seen)


def delta_rule(q, k, v, alpha, beta, state_dtype=None, swap=None):
    """q, k, alpha: (L, H, dk); v: (L, H, dv); beta: (L, H). Step 5, a token
    a step of a `lax.scan`; returns o (L, H, dv). `swap` = (t_save, t_swap)
    plants "neighbour_state": the state before token t_swap is replaced by
    the state before token t_save."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def update(S, q_t, k_t, v_t, a_t, b_t):
        S = a_t[:, :, None] * S  # row c of the state times alpha_c
        predicted = jnp.einsum("hkv,hk->hv", S, k_t, precision=HIGHEST)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - predicted)[:, None, :]
        if state_dtype is not None:
            # as a state kept in that precision (an explicit rounding: the
            # compiler may drop a cast there and back as excess precision)
            info = jnp.finfo(state_dtype)
            S = jax.lax.reduce_precision(S, info.nexp, info.nmant)
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HIGHEST)

    def token(S, xs):
        return update(S, *xs)

    def token_with_swap(carry, xs):
        S, kept = carry
        t, *step = xs
        kept = jnp.where(t == swap[0], S, kept)
        S, o = update(jnp.where(t == swap[1], kept, S), *step)
        return (S, kept), o

    zero = jnp.zeros((H, dk, dv), jnp.float32)
    if swap is None:
        return jax.lax.scan(token, zero, (q, k, v, alpha, beta))[1]
    return jax.lax.scan(token_with_swap, (zero, zero),
                        (jnp.arange(q.shape[0]), q, k, v, alpha, beta))[1]


def unit(a):
    """Step 2's norm: each head's vector over its length."""
    return a / jnp.linalg.norm(a, axis=-1, keepdims=True)


def kda_mixer(n, w, *, heads, head_dim, neg_eigval, eps, state_dtype=None, rule_dtype=None,
              fault=None, chunk=None, prompt=None):
    """Steps 1-6 on n = RMSNorm(x): (L, hidden)."""
    L = n.shape[0]
    H, d = heads, head_dim
    conv = w["conv"].astype(jnp.float32)  # (taps, 3 H d): q's, k's, v's channels
    tail = dict(fault=fault, chunk=chunk, prompt=prompt)
    q = jax.nn.silu(causal_conv(mm(n, w["wq"]), conv[:, :H * d], **tail))
    k = jax.nn.silu(causal_conv(mm(n, w["wk"]), conv[:, H * d:2 * H * d], **tail))
    v = jax.nn.silu(causal_conv(mm(n, w["wv"]), conv[:, 2 * H * d:], **tail))
    q, k, v = (a.reshape(L, H, d) for a in (q, k, v))
    q, k = unit(q) * d ** -0.5, unit(k)
    beta = jax.nn.sigmoid(mm(n, w["wb"])) * (2.0 if neg_eigval and fault != "beta_01" else 1.0)
    step = jax.nn.softplus(
        mm(mm(n, w["w_f1"]), w["w_f2"]).reshape(L, H, d) + w["dt_bias"].astype(jnp.float32))
    g = -jnp.exp(w["A_log"].astype(jnp.float32))[None, :, None] * step  # (L, H, dk)
    if fault == "scalar_decay":
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
    swap = None
    if fault == "neighbour_state":
        last = ((prompt if prompt is not None else L) - 1) // chunk * chunk
        swap = (last - chunk, last)
    q, k, v, alpha, beta = map(_rounded(rule_dtype), (q, k, v, jnp.exp(g), beta))
    o = delta_rule(q, k, v, alpha, beta, state_dtype, swap)
    gate = jax.nn.sigmoid(mm(mm(n, w["w_g1"]), w["w_g2"])).reshape(L, H, d)
    o = rms_norm(o, w["norm"], eps) * gate
    return mm(o.reshape(L, H * d), w["wo"])


def gqa_mixer(n, w, *, heads, kv_heads, fault=None):
    """NoPE grouped-query attention under its elementwise gate, on n =
    RMSNorm(x): (L, hidden)."""
    L = n.shape[0]
    q, k, v = mm(n, w["wq"]), mm(n, w["wk"]), mm(n, w["wv"])
    D = q.shape[-1] // heads
    q = q.reshape(L, kv_heads, heads // kv_heads, D)
    k, v = k.reshape(L, kv_heads, D), v.reshape(L, kv_heads, D)
    outs = []
    for start in range(0, L, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        s = jnp.einsum("lkrd,mkd->krlm", qb, k, precision=HIGHEST) / (D ** 0.5)
        rows = (start + jnp.arange(qb.shape[0]))[:, None]
        s = jnp.where((jnp.arange(L)[None, :] <= rows)[None, None], s, -jnp.inf)
        outs.append(jnp.einsum("krlm,mkd->lkrd", jax.nn.softmax(s, axis=-1), v,
                               precision=HIGHEST))
    a = jnp.concatenate(outs, axis=0).reshape(L, heads * D)
    gate = mm(n, w["w_out_gate"])
    if fault == "head_gate":
        gate = jnp.repeat(gate.reshape(L, heads, D).mean(axis=-1), D, axis=-1)
    return mm(a * jax.nn.sigmoid(gate), w["wo"])


def swiglu(x, w_gate, w_up, w_down):
    """In blocks of rows."""
    return jnp.concatenate([
        mm(jax.nn.silu(mm(rows, w_gate)) * mm(rows, w_up), w_down)
        for rows in (x[i:i + ROW_BLOCK] for i in range(0, x.shape[0], ROW_BLOCK))
    ], axis=0)


def sparse_ffn(n, w, *, top_k, scale, first_expert, router_dtype=None, routing=None,
               tie_margin=None, fault=None):
    """n: (L, hidden). Returns (the held experts' part of the weighted sum
    plus the shared expert, a dict of the (L, top_k) experts used, the (L,)
    margin between the last chosen and the first unchosen biased score,
    which tokens' told experts differed / were refused, and their `deficit`:
    how far below this router's own last chosen biased score the lowest of a
    token's told experts lies, 0 where none does or nothing was told: a
    `tie_margin` refuses the tokens whose deficit passes it). `routing` ((L,
    top_k) experts, -1 for none) takes the place of this router's own choice
    for the tokens where every told expert's biased score lies within
    `tie_margin` of the last chosen one's (None: for every token told); the
    weights are still this router's scores of them, without the bias."""
    cast = _rounded(router_dtype)
    scores = cast(jax.nn.sigmoid(cast(mm(cast(n), cast(w["router"].astype(jnp.float32))))))
    biased = scores + w["router_bias"].astype(jnp.float32)
    top_b, top_e = jax.lax.top_k(biased, top_k + 1)
    margin = top_b[:, top_k - 1] - top_b[:, top_k]
    top_b, top_e = top_b[:, :top_k], top_e[:, :top_k]
    differs = refused = jnp.zeros(n.shape[0], bool)
    deficit = jnp.zeros(n.shape[0], jnp.float32)
    if routing is not None:
        told = (routing >= 0).all(axis=-1)
        told_e = jnp.where(told[:, None], routing, top_e)
        told_b = jnp.take_along_axis(biased, told_e, axis=-1)
        sorted_e = jnp.sort(told_e, axis=-1)
        take = told & (sorted_e[:, 1:] != sorted_e[:, :-1]).all(axis=-1)
        if tie_margin is not None:
            take &= (told_b >= top_b[:, -1:] - tie_margin).all(axis=-1)
        differs = told & (sorted_e != jnp.sort(top_e, axis=-1)).any(axis=-1)
        deficit = jnp.maximum(top_b[:, -1] - told_b.min(axis=-1), 0.0)
        refused = told & ~take
        top_e = jnp.where(take[:, None], told_e, top_e)
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    weight = scale * top_s / (top_s.sum(axis=-1, keepdims=True) + 1e-20)
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
    if fault != "no_shared":
        y = y + swiglu(n, w["shared_gate"], w["shared_up"], w["shared_down"])
    return y, {"chosen": top_e, "margin": margin, "differs": differs, "refused": refused,
               "deficit": deficit}


def is_linear(cfg: dict, i: int) -> bool:
    """Layer i is a linear one unless `gqa_layers` lists it (the published
    list names every softmax layer of the uncut depth; indices at or past
    `num_hidden_layers` say nothing here)."""
    return i not in cfg["gqa_layers"]


def layer(x, w, cfg: dict, i: int, *, first_expert=0, state_dtype=None, rule_dtype=None,
          router_dtype=None, fault=None, chunk=None, prompt=None, routing=None, tie_margin=None):
    """One block on x: (L, hidden) float32; `w` as `glue/kda_moe.py` fills
    it. Returns (x, what `sparse_ffn` says of its routing)."""
    eps = cfg["rms_norm_eps"]
    n = rms_norm(x, w["attn_norm"], eps)
    if is_linear(cfg, i):
        lin = cfg["linear_attn_config"]
        if lin["num_kv_heads"] not in (None, lin["num_heads"]):
            raise ValueError("key and value head counts differ: not carried here")
        mixed = kda_mixer(
            n, w, heads=lin["num_heads"], head_dim=lin["head_dim"],
            neg_eigval=bool(cfg["kda_allow_neg_eigval"]), eps=eps, state_dtype=state_dtype,
            rule_dtype=rule_dtype, fault=fault, chunk=chunk, prompt=prompt)
    else:
        mixed = gqa_mixer(n, w, heads=cfg["num_attention_heads"],
                          kv_heads=cfg["num_key_value_heads"], fault=fault)
    h = x + mixed
    y, routed = sparse_ffn(
        rms_norm(h, w["mlp_norm"], eps), w, top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"], first_expert=first_expert,
        router_dtype=router_dtype, routing=routing, tie_margin=tie_margin, fault=fault)
    return h + y, routed


def head(x, final_norm, w_out, eps):
    """Final norm, then the logits against `w_out` (hidden, vocabulary) in
    blocks of the vocabulary."""
    x = rms_norm(x, final_norm, eps)
    return jnp.concatenate(
        [mm(x, w_out[:, v:v + VOCAB_BLOCK]) for v in range(0, w_out.shape[1], VOCAB_BLOCK)],
        axis=1)


def logits(tokens, embedding, layers, final_norm, w_out, cfg, last=None, record=None,
           state_dtype=None, rule_dtype=None, router_dtype=None, fault=None, chunk=None,
           prompt=None, routing=None, tie_margin=None, first_expert=0):
    """Float32 logits of the last `last` positions of one sequence.

    `layers` is an iterable that yields one layer's weight dict at a time;
    `cfg` is the configuration file (Hugging Face key names); `w_out` is
    (hidden, vocabulary). `first_expert` is the first of the contiguous
    experts the dicts hold. `routing` maps a layer's index to the (L, top_k)
    experts told for it (see `sparse_ffn`); left None, it is the system's
    where `cfg["model"]["check"]` asks for that and `layers` can say, with
    the configuration's `tie_margin`. `chunk` and `prompt` place the planted
    hand-over faults (the module's docstring); left None they are the
    check's own (`model.check.replay`)."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r}")
    check = cfg.get("model", {}).get("check") or {}
    replay = check.get("replay") or {}
    if chunk is None:
        chunk = replay.get("prefill_chunk_tokens")
    if prompt is None and "decoded_tail" in replay:
        prompt = len(tokens) - replay["decoded_tail"]
    asked = routing is None and check.get("routing") == "system"
    if asked and hasattr(layers, "system_routing"):
        routing, tie_margin = layers.system_routing(tokens, cfg), check["tie_margin"]
    x = jnp.take(embedding, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    told = differs = refused = 0
    for i, w in enumerate(layers):
        given = (routing or {}).get(i)
        x, routed = layer(
            x, w, cfg, i, first_expert=first_expert, state_dtype=state_dtype,
            rule_dtype=rule_dtype, router_dtype=router_dtype, fault=fault, chunk=chunk,
            prompt=prompt,
            routing=None if given is None else jnp.asarray(given), tie_margin=tie_margin)
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
