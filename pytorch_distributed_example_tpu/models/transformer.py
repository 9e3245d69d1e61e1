"""TransformerLM — the framework's flagship model (Llama-style decoder,
BERT-style encoder via `causal=False`).

Covers BASELINE.json configs #4/#5 ("BERT-base fine-tune", "Llama-3-8B
FSDP full-shard → GSPMD"; SURVEY.md §6). TPU-native design:

* RMSNorm + RoPE + SwiGLU + grouped-query attention (Llama topology);
* attention runs the Pallas flash kernel (`ops/flash_attention.py`;
  compiled on TPU, interpreted elsewhere), dense softmax when disabled
  or when the shape cannot be tiled (warned);
* bf16-friendly: params fp32, activations cast to `dtype`, logits fp32;
* `sharding_rules()` emits the canonical 2-D Megatron(+ZeRO) GSPMD layout
  (scaling-book recipe): attention/MLP in-features over ``fsdp``,
  head/ffn out-features over ``tp`` — XLA inserts the one all-reduce per
  block pair that Megatron hand-codes;
* `nn.remat` per block when `remat=True`: the backward recomputes a
  block's forward except the values a rung of `utils/remat.py`'s ladder
  keeps (the flash output and log-sum-exp; + q/k/v and the mid residual;
  + the MLP's gate and up products), named here where they are made. The
  rung is the richest at which the TPU compiler says the trainer's step
  fits the chip (`utils.remat.fitted`); with no trainer around the
  trace, or no limit reported, a block keeps nothing.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..utils import remat as _remat


@dataclass(frozen=True)
class RopeSpec:
    """One rotary embedding: its base, the leading share of a head it
    rotates (the rest passes unrotated; 0.0 rotates nothing: a model
    whose attention takes no position), and YaRN's five numbers
    (factor, original_max_position_embeddings, beta_fast, beta_slow,
    attention_factor) where the frequencies are stretched. `softmax_factor`
    multiplies a LATENT layer's softmax scale: the family that stretches a
    latent layer's rope puts YaRN's factor there, squared
    ((0.1 mscale_all_dim ln factor + 1)^2), and not on cos and sin."""

    theta: float = 10000.0
    rotary_fraction: float = 1.0
    yarn: Optional[Tuple[float, int, float, float, float]] = None
    softmax_factor: float = 1.0


# what a layer's mixer keeps between calls: every key and value, those of
# a window, one STATE BLOCK a row whose leaves the mixer names (a linear
# layer's recurrent state and conv tail, a conv layer's tail alone), or one
# compressed latent a token. A kind's name is the `LayerSpec.attention` of
# the layers that keep it. A kind is registered in two places: `cache_leaves`
# below (what its mixer keeps) and its record in `serve/kinds.py` (what the
# serve plane does with that).
CACHE_KINDS = ("full", "window", "linear", "latent", "conv")
# tokens of one sub-chunk of a linear layer's chunked scan
# (`gated_delta_chunked`): a power of two that divides every prefill
# bucket of the serve cells (128, 256, 512)
LINEAR_CHUNK = 64
# tokens of one block of a sub-chunk under a decay a key channel
# (`_channel_decayed_pairs`): inside a block the decayed dot products are
# summed channel by channel, between blocks they are matrix products
LINEAR_BLOCK = 16
# normalisation pairs of a hyper-connection map written out a trip of its
# loop (`HyperConnection._sinkhorn`)
SINKHORN_UNROLL = 5


@dataclass(frozen=True)
class LayerSpec:
    """What one layer of a patterned model is. `attention` is "full",
    "window" (the last `cfg.window` keys), "linear" (`LinearAttention`
    at the `linear_*` sizes: a recurrent state, no keys and values) or
    "latent" (`LatentAttention` at the `latent_*` sizes: one compressed
    latent and one shared rotary key a token, no K and V heads) or "conv"
    (`GatedConv`: a gated short convolution of `cfg.conv_taps` taps, which
    keeps the `conv_taps - 1` inputs behind a row's last token and nothing
    else);
    `n_heads` and `rope` default to the model's; `mlp` is "dense" (SwiGLU
    at `cfg.ffn_dim`) or "sparse" (`SparseMoE` at the `sparse_*` sizes)."""

    attention: str = "full"
    n_heads: Optional[int] = None
    rope: Optional[RopeSpec] = None
    mlp: str = "dense"


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None = MHA; < n_heads = GQA
    d_ff: Optional[int] = None  # None = 4 * d_model (SwiGLU sizes 2/3 * that)
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    causal: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    use_flash: bool = True
    # per-block jax.checkpoint; WHAT a block keeps is the most the chip has
    # room for, found by the trainer that builds the step (utils/remat.py)
    remat: bool = False
    n_experts: int = 0  # > 0 switches the MLP to a top-k MoE
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1  # 1 = Switch, 2 = GShard/Mixtral-style
    # -- a layer pattern (all None/0: n_layers blocks alike, as above) -----
    head_size: Optional[int] = None  # None = d_model // n_heads
    layers: Optional[Tuple[LayerSpec, ...]] = None  # one entry a layer
    window: Optional[int] = None  # keys a "window" layer attends
    attn_gate: bool = False  # per-head sigmoid gate on the attention output
    # an ELEMENTWISE sigmoid gate on the attention output, from a projection
    # of its own of the output's full width (the two gates refuse each other)
    attn_out_gate: bool = False
    rope_pairs: str = "interleaved"  # or "halves" (the Hugging Face port)
    # the "sparse" MLP: dropless top-k over `sparse_experts` SwiGLU experts
    # of width `sparse_d_ff`, weights normalised then times `routed_scale`,
    # plus one shared SwiGLU of width `shared_d_ff` (0: none) unweighted.
    # `experts_held` = (first, count): the contiguous experts this chip
    # holds of a layer (None: all) - it routes over all of them and
    # computes its own experts' part.
    sparse_experts: int = 0
    sparse_top_k: int = 0
    sparse_d_ff: int = 0
    shared_d_ff: int = 0
    routed_scale: float = 1.0
    experts_held: Optional[Tuple[int, int]] = None
    # the "linear" mixer (`LinearAttention`, a gated delta rule):
    # `linear_heads` heads of `linear_key_dim`-wide queries and keys and
    # `linear_value_dim`-wide values behind a causal depthwise conv of
    # `linear_conv` taps; beta reaches (0, 2) with `linear_neg_eigval`,
    # else (0, 1); more than one token is scanned in sub-chunks of
    # `LINEAR_CHUNK`
    linear_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    linear_neg_eigval: bool = False
    # what a linear layer's state is decayed by: "head" is one alpha a head
    # and token (Gated DeltaNet: full projections for the decay and for a
    # silu output gate), "channel" one alpha a KEY CHANNEL of a head, a
    # vector of `linear_key_dim` (Kimi Delta Attention: a sigmoid output
    # gate; the decay and that gate through low-rank pairs of
    # `linear_gate_rank`, which "channel" needs and "head" refuses)
    linear_decay: str = "head"
    linear_gate_rank: int = 0
    # each sublayer's OUTPUT is normed before the residual add,
    # h = x + norm(mixer(x)), and nothing norms its input
    post_norm: bool = False
    # q and k of an attention layer are RMS-normed over the whole
    # projection, before the heads are split
    qk_norm: bool = False
    # q and k of an attention layer are RMS-normed over EACH head's
    # `head_dim` values, after the heads are split (one scale of
    # `head_dim` values for q and one for k, every head's)
    qk_head_norm: bool = False
    # each sublayer is normed on its input AND on its output,
    # h = x + post_norm(mixer(norm(x))): four norms a block
    sandwich_norm: bool = False
    # how the "sparse" MLP's router scores the experts: "softmax" over all
    # of them, or "sigmoid" of each (both: the top k, normalised to sum to
    # 1, times `routed_scale`)
    sparse_score: str = "softmax"
    # the "latent" mixer (`LatentAttention`, multi-head latent attention):
    # queries through a normed bottleneck of `latent_q_rank`; a token's
    # keys and values through ONE normed latent of `latent_kv_rank` values
    # that every head up-projects (`latent_nope_dim` of a key,
    # `latent_v_dim` of a value), and ONE rotary key of `latent_rope_dim`
    # values that every head shares
    latent_q_rank: int = 0
    latent_kv_rank: int = 0
    latent_nope_dim: int = 0
    latent_rope_dim: int = 0
    latent_v_dim: int = 0
    # the router of the "sparse" MLP CHOOSES its top k by score plus a
    # learned bias an expert; the weights are the scores without it
    sparse_choice_bias: bool = False
    # residual streams of a pattern's blocks (`HyperConnection`): 1 is the
    # one residual add; above it a token's state between sublayers is
    # (`hc_mult`, d_model) (the blocks pass (`hc_mult`, B, L, d_model)),
    # each sublayer reads a learned mixture of the
    # streams and writes back through a second map while a doubly
    # stochastic matrix (`hc_sinkhorn_iters` row and column normalisations
    # of exp of logits clipped to `hc_clamp`, `hc_eps` in every
    # denominator) remixes them
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    # the "conv" mixer (`GatedConv`): taps of its causal depthwise conv
    conv_taps: int = 3
    # what the "sparse" MLP's router adds to the sum of the chosen scores
    # before it divides them by it
    sparse_norm_eps: float = 1e-20
    # the logits are the final norm's output against the EMBEDDING's
    # transpose: the model has no `lm_head` of its own
    tie_embeddings: bool = False

    def __post_init__(self):
        if self.rope_pairs not in ("interleaved", "halves"):
            raise ValueError(f"rope_pairs {self.rope_pairs!r}")
        if self.sparse_score not in ("softmax", "sigmoid"):
            raise ValueError(f"sparse_score {self.sparse_score!r}")
        if self.linear_decay not in ("head", "channel"):
            raise ValueError(f"linear_decay {self.linear_decay!r}")
        if bool(self.linear_gate_rank) != (self.linear_decay == "channel"):
            raise ValueError(
                "linear_gate_rank belongs to linear_decay 'channel', which needs one"
            )
        if self.attn_gate and self.attn_out_gate:
            raise ValueError("attn_gate and attn_out_gate are two gates of one output")
        if self.post_norm and self.sandwich_norm:
            raise ValueError("post_norm and sandwich_norm are two placements")
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm and qk_head_norm are two norms of q and k")
        if self.hc_mult < 1 or (self.hc_mult > 1 and self.layers is None):
            raise ValueError(
                f"hc_mult {self.hc_mult}: residual streams belong to a layer pattern"
            )
        if self.layers is None:
            return
        if len(self.layers) != self.n_layers:
            raise ValueError(
                f"{len(self.layers)} layer specs for n_layers={self.n_layers}"
            )
        for i, spec in enumerate(self.layers):
            if spec.attention not in CACHE_KINDS or spec.mlp not in (
                "dense", "sparse"
            ):
                raise ValueError(f"layer {i}: {spec}")
            if spec.attention == "window" and not self.window:
                raise ValueError(f"layer {i} is a window layer and window is unset")
            if spec.attention == "linear":
                if not (
                    self.linear_heads and self.linear_key_dim
                    and self.linear_value_dim and self.linear_conv >= 1
                ):
                    raise ValueError(
                        f"layer {i} is linear and linear_heads/key_dim/"
                        "value_dim/conv are unset"
                    )
            elif spec.attention == "conv":
                if self.conv_taps < 2:
                    raise ValueError(f"layer {i} is conv and conv_taps < 2")
            elif spec.attention == "latent":
                if not (
                    self.latent_q_rank and self.latent_kv_rank
                    and self.latent_nope_dim and self.latent_v_dim
                    and self.latent_rope_dim and self.latent_rope_dim % 2 == 0
                ):
                    raise ValueError(
                        f"layer {i} is latent and latent_q_rank/kv_rank/"
                        "nope_dim/rope_dim/v_dim are unset"
                    )
            elif (spec.n_heads or self.n_heads) % self.kv_heads:
                raise ValueError(f"layer {i}: heads do not divide over kv_heads")
            if spec.mlp == "sparse" and not (
                self.sparse_experts >= self.sparse_top_k > 0 and self.sparse_d_ff
            ):
                raise ValueError(
                    f"layer {i} is sparse and sparse_experts/top_k/d_ff are unset"
                )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_heads

    def layer(self, i: int) -> Optional[LayerSpec]:
        """Layer i's spec with the model's defaults filled in, or None for
        a model without a pattern (every consumer then takes the path it
        took before patterns existed)."""
        if self.layers is None:
            return None
        spec = self.layers[i]
        return LayerSpec(
            spec.attention, spec.n_heads or self.n_heads,
            spec.rope or RopeSpec(self.rope_theta), spec.mlp,
        )

    @property
    def window_layers(self) -> Tuple[bool, ...]:
        """Per layer: does it keep a window of K/V only."""
        if self.layers is None:
            return (False,) * self.n_layers
        return tuple(spec.attention == "window" for spec in self.layers)

    @property
    def linear_layers(self) -> Tuple[int, ...]:
        """The layers that keep a recurrent state and no K/V."""
        if self.layers is None:
            return ()
        return tuple(
            i for i, spec in enumerate(self.layers) if spec.attention == "linear"
        )

    @property
    def latent_layers(self) -> Tuple[int, ...]:
        """The layers that keep one latent a token and no K and V heads."""
        if self.layers is None:
            return ()
        return tuple(
            i for i, spec in enumerate(self.layers) if spec.attention == "latent"
        )

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        """The layers that keep the tail of a short convolution and no K/V."""
        if self.layers is None:
            return ()
        return tuple(
            i for i, spec in enumerate(self.layers) if spec.attention == "conv"
        )

    @property
    def latent_width(self) -> int:
        """Values a latent layer caches a token: the latent and the
        shared rotary key."""
        return self.latent_kv_rank + self.latent_rope_dim

    @property
    def cache_kinds(self) -> Tuple[str, ...]:
        """The kinds of cached state the layers keep, in `CACHE_KINDS`'
        order: where there is more than one, a paged call's
        `block_tables` is the tuple of one table a kind, in this order."""
        if self.layers is None:
            return ("full",)
        have = {spec.attention for spec in self.layers}
        return tuple(kind for kind in CACHE_KINDS if kind in have)

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        if self.layers is None:
            return ()
        return tuple(i for i, spec in enumerate(self.layers) if spec.mlp == "sparse")

    @property
    def ffn_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        # Llama convention: 2/3 * 4d rounded to a multiple of 128
        d = int(2 * 4 * self.d_model / 3)
        return (d + 127) // 128 * 128


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * scale).astype(x.dtype)


def rope_freqs(head_dim: int, max_len: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    ang = jnp.outer(t, inv)  # (L, head_dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def rope_table(spec: RopeSpec, head_dim: int, max_len: int):
    """(cos, sin), each (max_len, r / 2), of one `RopeSpec`: r =
    `rotary_fraction * head_dim` leading values of a head rotate. With
    `yarn` the inverse frequencies are blended between theta^(-2i/r) and
    that over `factor` by the linear ramp between the two correction
    dimensions (where `original_max` positions make `beta_fast` and
    `beta_slow` turns), and cos and sin carry `attention_factor`."""
    import math

    r = int(head_dim * spec.rotary_fraction)
    if spec.yarn is None:
        return rope_freqs(r, max_len, spec.theta)
    factor, original_max, beta_fast, beta_slow, attention_factor = spec.yarn

    def correction_dim(turns):
        return r * math.log(original_max / (turns * 2 * math.pi)) / (
            2 * math.log(spec.theta)
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), r - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(r // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    plain = 1.0 / (spec.theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    inv = plain / factor * ramp + plain * (1.0 - ramp)
    ang = jnp.outer(jnp.arange(max_len, dtype=jnp.float32), inv)
    return jnp.cos(ang) * attention_factor, jnp.sin(ang) * attention_factor


def _rotate(x, c, s, halves: bool):
    """Rotate the leading `2 * c.shape[-1]` values of every head of x by
    the angles whose cos/sin are c/s (already broadcastable to x's
    pairs); values past them pass. Pairs are (even, odd) neighbours, or
    with `halves` value j and value j + r/2."""
    half, D = c.shape[-1], x.shape[-1]
    if not half:  # a `RopeSpec` that rotates nothing
        return x
    whole = 2 * half == D
    xr = x if whole else x[..., : 2 * half]
    if halves:
        x1, x2 = xr[..., :half], xr[..., half:]
        rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    else:
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        rot = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(
            xr.shape
        )
    rot = rot.astype(x.dtype)
    return rot if whole else jnp.concatenate([rot, x[..., 2 * half:]], axis=-1)


def apply_rope(x, cos, sin, halves: bool = False):
    """x: (B, L, H, D); rotate pairs by position angle (see `_rotate`)."""
    with jax.named_scope("rope"):
        return _rotate(x, cos[None, :, None, :], sin[None, :, None, :], halves)


def apply_rope_batched(x, cos, sin, halves: bool = False):
    """x: (B, L, H, D); cos/sin: (B, L, r/2) — per-SAMPLE position
    angles, for decode batches where every row sits at its own absolute
    position (the serve engine's slot batch)."""
    with jax.named_scope("rope"):
        return _rotate(x, cos[:, :, None, :], sin[:, :, None, :], halves)


def _dense_attention(q, k, v, causal, scale):
    from ..ops.reference import dense_attention

    with jax.named_scope("dense_attention"):
        return dense_attention(q, k, v, causal=causal, scale=scale)


def _paged_write_index(pos, block_tables, nblk: int, bs: int):
    """(B * L,) flat pool row of each token at absolute position `pos`
    (B, L) under `block_tables` (B, nb): physical block times `bs` plus the
    offset in it, or `nblk * bs` (out of bounds: a `mode="drop"` scatter
    discards the write) where the logical block is unallocated (entry ==
    nblk) or past the table."""
    nb = block_tables.shape[1]
    lb = pos // bs  # (B, L) logical block
    off = pos % bs
    phys = jnp.take_along_axis(
        block_tables, jnp.clip(lb, 0, nb - 1), axis=1
    )  # (B, L) physical block id, == nblk when unallocated
    flat = jnp.where(lb < nb, phys * bs + off, nblk * bs)  # OOB sentinel
    return flat.reshape(-1)


def _position_mask(q_pos, key_pos, window=None):
    """(..., L, M) bool: key j is attended by query i when j <= i and,
    with a window, i - window < j. Positions are absolute; leading axes
    of `q_pos` (..., L) and `key_pos` (..., M) broadcast."""
    mask = key_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        mask &= key_pos[..., None, :] > q_pos[..., :, None] - window
    return mask


def _grouped_attention(q, k, v, scale, mask):
    """Masked softmax attention against UN-repeated K/V: q (B, L, H, Dh),
    k/v (B, M, KV, Dh), mask (B or 1, L, M). Scores and softmax in
    float32, probabilities cast to v's dtype; returns (B, L, H * Dh)."""
    B, L, H, Dh = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, L, KV, H // KV, Dh)
    s = jnp.einsum("blkrd,bmkd->bkrlm", qg, k) * scale  # (B,KV,rep,L,M)
    s = jnp.where(mask[:, None, None], s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkrlm,bmkd->blkrd", p, v).reshape(B, L, H * Dh)


class Attention(nn.Module):
    """`spec` (a resolved `LayerSpec`, `cfg.layer(i)`) says what this
    layer is in a patterned model: its query heads, and whether it
    attends a window. None is a model without a pattern: `cfg.n_heads`
    heads, full attention, no gate, and nothing below reads a pattern
    field of `cfg`."""

    cfg: TransformerConfig
    spec: Optional[LayerSpec] = None

    @property
    def heads(self) -> int:
        return self.cfg.n_heads if self.spec is None else self.spec.n_heads

    @property
    def window(self) -> Optional[int]:
        """Keys a query attends, itself included: position i sees j with
        i - window < j <= i. None: every j <= i."""
        if self.spec is None or self.spec.attention != "window":
            return None
        return self.cfg.window

    @property
    def rope_halves(self) -> bool:
        return self.spec is not None and self.cfg.rope_pairs == "halves"

    @nn.nowrap
    def _project_out(self, o, x, dense):
        """(B, L, H * Dh) attention output -> the block's residual term:
        each head (`attn_gate`) or each value (`attn_out_gate`) times its
        sigmoid gate where the model has one, then `o_proj`."""
        if self.spec is not None and self.cfg.attn_gate:
            B, L, _ = o.shape
            H = self.heads
            with jax.named_scope("attn_gate"):
                g = jax.nn.sigmoid(dense(H, "head_gate")(x))  # (B, L, H)
                o = (o.reshape(B, L, H, -1) * g[..., None].astype(o.dtype)).reshape(
                    B, L, -1
                )
        elif self.spec is not None and self.cfg.attn_out_gate:
            with jax.named_scope("attn_gate"):
                g = jax.nn.sigmoid(dense(o.shape[-1], "out_gate")(x))  # (B, L, H * Dh)
                o = o * g.astype(o.dtype)
        return dense(self.cfg.d_model, "o_proj")(o)

    @nn.compact
    def __call__(
        self, x, cos, sin, decode: bool = False, positions=None,
        block_tables=None,
    ):
        cfg = self.cfg
        B, L, _ = x.shape
        H, KV, Dh = self.heads, cfg.kv_heads, cfg.head_dim
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, name=name
        )
        # q and k normed over the whole projection where the pattern says so
        qk_norm = (
            (lambda name, a: RMSNorm(cfg.norm_eps, name=name)(a))
            if self.spec is not None and cfg.qk_norm else (lambda name, a: a)
        )
        q = qk_norm("q_norm", dense(H * Dh, "q_proj")(x)).reshape(B, L, H, Dh)
        k = qk_norm("k_norm", dense(KV * Dh, "k_proj")(x)).reshape(B, L, KV, Dh)
        v = dense(KV * Dh, "v_proj")(x).reshape(B, L, KV, Dh)
        if self.spec is not None and cfg.qk_head_norm:
            # over each head's own values: one scale of Dh for q, one for k
            q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        scale = 1.0 / (Dh ** 0.5)

        if decode:
            o = self._decode(
                q, k, v, cos, sin, scale, positions, block_tables
            )
            return self._project_out(o, x, dense)

        q = apply_rope(q, cos, sin, self.rope_halves)
        k = apply_rope(k, cos, sin, self.rope_halves)
        # named BEFORE the GQA repeat: the repeat is a copy and may be
        # recomputed, so a saved k/v costs KV heads and not H
        q = checkpoint_name(q, _remat.ATTN_Q)
        k = checkpoint_name(k, _remat.ATTN_K)
        v = checkpoint_name(v, _remat.ATTN_V)
        if self.window is not None:
            # the dense masked path; a window inside the flash kernel
            # comes with the training cell that needs it
            if cfg.use_flash:
                _flash_ok(L, Dh, self.window)
            with jax.named_scope("window_attention"):
                o = _grouped_attention(
                    q, k, v, scale,
                    _position_mask(jnp.arange(L), jnp.arange(L), self.window)[None],
                )
            return self._project_out(o, x, dense)
        if KV != H:  # GQA: repeat kv groups to full heads
            rep = H // KV
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if cfg.use_flash and _flash_ok(L, Dh):
            from ..ops import flash_attention

            with jax.named_scope("flash_attention"):
                o = flash_attention(q, k, v, causal=cfg.causal, scale=scale)
        else:
            o = _dense_attention(q, k, v, cfg.causal, scale)
        o = o.reshape(B, L, H * Dh)
        return self._project_out(o, x, dense)

    def _decode(
        self, q, k, v, cos, sin, scale, positions=None, block_tables=None,
    ):
        """KV-cache step: write this call's K/V at the running index into
        static (B, max_seq_len) buffers (flax "cache" collection), attend
        causally over the cache. One code path serves prefill (L = prompt
        length at index 0) and decode (L = 1) — static shapes throughout,
        so XLA compiles exactly two programs for the whole generate loop.
        cos/sin must cover max_seq_len; RoPE uses ABSOLUTE positions via a
        dynamic slice at the cache index. This is `generate()`'s cache and
        the serve tests' token-exact reference.

        `block_tables` ((B, nb) int32) with `positions` ((B,) int32)
        switches the cache variables to the serving layout: a PAGED block
        pool shared by every row, each row an independent request at its
        own depth. k/v are (num_blocks, block_size, KV, Dh) and
        row b's logical block j lives at physical block
        `block_tables[b, j]`; row b's K/V land at positions[b] and row b
        attends keys <= its own position. Writes scatter each token to
        (block, offset) through a flat view — positions whose logical
        block is unallocated (table entry == num_blocks) or out of range
        fall out of bounds and are DROPPED, which is what lets a parked
        (retired) slot lane and a padded prefill chunk ride through the
        step without touching any live request's blocks. Reads attend
        the row's logical layout under the same absolute-position causal
        mask, by one of the paths `_decode_paged` describes; there is
        no "index" variable on this path (the pool has no per-row
        cursor). Either argument without the other is refused.

        Returns the attention output (B, L, H * Dh), before the gate and
        `o_proj`. A window layer masks the same buffers by
        `i - window < j <= i`."""
        from jax import lax

        cfg = self.cfg
        if not cfg.causal:
            raise ValueError(
                "decode=True requires a causal model (the KV-cache step "
                "attends positions <= index); causal=False configs have "
                "no autoregressive decode"
            )
        B, L, KV, Dh = k.shape
        H = self.heads
        M = cfg.max_seq_len
        halves = self.rope_halves
        # flax decode-cache convention: during init (variables not yet
        # present) only CREATE them — persisting the write would hand the
        # caller a cache whose index already advanced past the init input
        is_initialized = self.has_variable("cache", "k")
        if block_tables is not None:
            if positions is None:
                raise ValueError("block_tables requires positions")
            if not is_initialized:
                raise ValueError(
                    "paged decode needs a pre-built block-pool cache tree "
                    "(serve.cache.init_paged_cache) passed via apply(); "
                    "the module cannot size the pool from the batch"
                )
            from ..ops.paged_attention import from_pool_heads, to_pool_heads

            # the pool may hold more KV heads than the model has
            # (`ops.paged_attention.pool_kv_heads`)
            held, width = self.get_variable("cache", "k").shape[2:]
            if width != Dh:  # several heads a row (`_decode_paged` packs
                held = KV  # them behind the rope): such a pool pads none
            q, k, v = to_pool_heads(q, k, v, held)
            o = self._decode_paged(
                q, k, v, cos, sin, scale, positions, block_tables
            )
            return from_pool_heads(o, KV, held)
        if positions is not None:
            raise ValueError(
                "positions without block_tables: per-row cache positions "
                "exist only on the paged pool (pass block_tables=); the "
                "dense cache keeps one scalar index"
            )
        ck = self.variable(
            "cache", "k", jnp.zeros, (B, M, KV, Dh), k.dtype
        )
        cv = self.variable(
            "cache", "v", jnp.zeros, (B, M, KV, Dh), v.dtype
        )
        ci = self.variable(
            "cache", "index", lambda: jnp.zeros((), jnp.int32)
        )
        key_pos = jnp.arange(M)
        idx = ci.value
        pos_cos = lax.dynamic_slice_in_dim(cos, idx, L, axis=0)
        pos_sin = lax.dynamic_slice_in_dim(sin, idx, L, axis=0)
        q = apply_rope(q, pos_cos, pos_sin, halves)
        k = apply_rope(k, pos_cos, pos_sin, halves)
        with jax.named_scope("kv_scatter"):
            kf = lax.dynamic_update_slice_in_dim(ck.value, k, idx, axis=1)
            vf = lax.dynamic_update_slice_in_dim(cv.value, v, idx, axis=1)
        if is_initialized:
            ck.value = kf
            cv.value = vf
            ci.value = idx + L
        q_pos = idx + jnp.arange(L)
        # causal over cache; (1, L, M) broadcast over batch
        mask = _position_mask(q_pos, key_pos, self.window)[None]
        # GQA: group the query heads and attend against the UN-repeated
        # cache — repeating the (B, M, KV, Dh) buffers up to H heads per
        # step would forfeit the KV-cache bandwidth saving GQA exists for
        with jax.named_scope("cache_attention"):
            return _grouped_attention(q, kf, vf, scale, mask)

    def _decode_paged(
        self, q, k, v, cos, sin, scale, positions, block_tables
    ):
        """The paged-pool cache step (see _decode).

        The cache collection holds ONE (num_blocks, block_size, KV, Dh)
        K/V pool shared by all B rows; `block_tables` (B, nb) maps each
        row's logical blocks onto it. Token at absolute position p of
        row b writes to flat pool index
        `block_tables[b, p // bs] * bs + p % bs`; invalid logical blocks
        (table entry == num_blocks) and positions past the table push
        the flat index out of bounds, where `mode="drop"` discards the
        write.

        Attention then takes one of three paths, chosen from what this
        call can see (`ops.paged_kernel`: query length, pool shape and
        dtype, table shape, the layer's window — no option names it). On
        a pool that is not quantized, at a head size Mosaic tiles (128
        lanes and multiples; a 64-wide head's pool holds two heads a row,
        `ops.paged_attention.pool_head_pack`: q, k and v take that layout
        behind the rope and the output is unpacked on the way back), a
        DECODE call (L == 1) runs `ops.paged_decode_attention` and a
        PREFILL CHUNK (L > 1, no window, a query length that fills
        sublane tiles) `ops.paged_chunk_attention`: kernels that read
        each row's pages out of the pool, as many as the row's last
        position and leading valid table entries give it. Every other
        call (int8 pools, the tiny head sizes and 4-token buckets of the
        CPU tests, a window layer's chunk) gathers the row's logical K/V
        layout (`ops.gather_paged_kv`) and masks a dense einsum by
        absolute position. On every path dropped or garbage regions are
        never attended (every key <= a live row's position sits in an
        allocated block — the engine allocates before it writes), and
        the output of a parked row or a padded query is finite and
        ignored by the scheduler.

        A QUANTIZED pool (int8 k/v plus `k_scale`/`v_scale` planes —
        `serve/cache.py::init_paged_cache(quantized=True)`) is detected
        from the cache collection: writes quantize each token's K/V
        vector per kv-head (`ops.quant.quantize_kv`) and scatter value
        and scale through the SAME flat index (same drop semantics);
        reads dequantize inside `ops.gather_paged_kv`, so the scores/
        softmax/output math below is identical in both modes.

        A WINDOW layer (`self.window`) runs the decode kernel and the
        gather over its own pool and table (`serve/cache.py` frees a
        row's blocks behind the window while the request lives, so
        leading table entries may be invalid): the kernel starts each
        row at its first attended page, and the gather takes the
        `window + L` keys the call can attend (`ops.paged_window_span`)
        and not the table's span. Both sit under a `window_attention`
        scope around `cache_attention`."""
        import contextlib

        from ..ops import (
            gather_paged_kv,
            paged_chunk_attention,
            paged_decode_attention,
            paged_kernel,
            paged_window_span,
        )
        from ..ops.paged_attention import pack_pool_heads, unpack_pool_heads
        from ..ops.quant import quantize_kv

        cfg = self.cfg
        B, L, KV, Dh = k.shape
        H = q.shape[2]  # the layer's, or with a padded pool its groups'
        M = cfg.max_seq_len
        window, halves = self.window, self.rope_halves
        quantized = self.has_variable("cache", "k_scale")
        ck = self.variable("cache", "k", lambda: None)
        cv = self.variable("cache", "v", lambda: None)
        if quantized:
            cks = self.variable("cache", "k_scale", lambda: None)
            cvs = self.variable("cache", "v_scale", lambda: None)
        nblk, bs = ck.value.shape[0], ck.value.shape[1]
        nb = block_tables.shape[1]

        idx = positions.astype(jnp.int32)  # (B,) absolute start positions
        pos = idx[:, None] + jnp.arange(L)[None, :]  # (B, L) absolute
        safe = jnp.clip(pos, 0, M - 1)  # RoPE table bound; overshoot is
        q = apply_rope_batched(q, cos[safe], sin[safe], halves)  # dropped below
        k = apply_rope_batched(k, cos[safe], sin[safe], halves)

        pack = ck.value.shape[3] // Dh
        if pack > 1:
            # a pool of 64-wide heads holds two a lane row
            # (`ops.paged_attention.pool_head_pack`): behind the rope, which
            # turns a head's own values, the operands take the pool's layout
            q, k, v = pack_pool_heads(q, k, v, pack)
            unpacked = functools.partial(
                unpack_pool_heads, kv_heads=KV, pack=pack, head_dim=Dh
            )
            KV, Dh = k.shape[2:]
        else:
            unpacked = lambda o: o

        flat = _paged_write_index(pos, block_tables, nblk, bs)

        def scatter(pool, upd):
            flat_pool = pool.reshape(nblk * bs, KV, Dh)
            flat_pool = flat_pool.at[flat].set(
                upd.reshape(B * L, KV, Dh), mode="drop"
            )
            return flat_pool.reshape(nblk, bs, KV, Dh)

        def scatter_scale(pool, upd):
            flat_pool = pool.reshape(nblk * bs, KV)
            flat_pool = flat_pool.at[flat].set(
                upd.reshape(B * L, KV), mode="drop"
            )
            return flat_pool.reshape(nblk, bs, KV)

        with jax.named_scope("kv_scatter"):
            if quantized:
                # quantize-on-scatter: post-RoPE K and V, one scale per
                # (token, kv-head); value and scale ride the same flat
                # index so a dropped write drops both
                qk, sk = quantize_kv(k)
                qv, sv = quantize_kv(v)
                ck.value = scatter(ck.value, qk)
                cv.value = scatter(cv.value, qv)
                cks.value = scatter_scale(cks.value, sk)
                cvs.value = scatter_scale(cvs.value, sv)
            else:
                ck.value = scatter(ck.value, k)
                cv.value = scatter(cv.value, v)
        scope = (
            jax.named_scope("window_attention") if window is not None
            else contextlib.nullcontext()
        )
        kernel = paged_kernel(L, ck.value, block_tables, window)
        if kernel is not None:
            with scope, jax.named_scope("cache_attention"):
                if kernel == "decode":
                    o = paged_decode_attention(
                        q[:, 0], ck.value, cv.value, block_tables, idx,
                        scale, window=window,
                    )
                else:
                    o = paged_chunk_attention(
                        q, ck.value, cv.value, block_tables, idx, scale
                    )
                return unpacked(o.reshape(B, L, H * Dh))
        first_block = n_blocks = None
        key0 = jnp.zeros((B,), jnp.int32)
        if window is not None:
            first_block, n_blocks = paged_window_span(idx, L, window, bs, nb)
            key0 = first_block * bs
        with scope:
            with jax.named_scope("kv_gather"):
                kf, vf = gather_paged_kv(
                    ck.value, cv.value, block_tables,
                    k_scale=cks.value if quantized else None,
                    v_scale=cvs.value if quantized else None,
                    out_dtype=cfg.dtype,
                    first_block=first_block, n_blocks=n_blocks,
                )
            with jax.named_scope("cache_attention"):
                # logical key span gathered: the tables' (>= M), or a
                # window layer's `n_blocks` from its first attended block
                key_pos = key0[:, None] + jnp.arange(kf.shape[1])[None, :]
                mask = _position_mask(pos, key_pos, window)  # (B, L, Mb)
                return unpacked(_grouped_attention(q, kf, vf, scale, mask))

def _latent_attention(q, latents, rank, scale, mask):
    """Masked softmax attention in the ABSORBED form of a latent layer:
    q (B, L, H, W) against the cached rows `latents` (B, M, W), whose
    first `rank` values are also the values; mask (B or 1, L, M). Scores
    and softmax in float32, probabilities cast to the latents' dtype;
    returns (B, L, H, rank)."""
    s = jnp.einsum("blhw,bmw->bhlm", q, latents) * scale
    s = jnp.where(mask[:, None], s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(latents.dtype)
    return jnp.einsum("bhlm,bmr->blhr", p, latents[..., :rank])


class LatentAttention(nn.Module):
    """The "latent" mixer of a layer pattern: multi-head latent attention
    (DeepSeek-V2, arXiv:2405.04434, section 2.1). Per token, with H heads,
    r = `latent_kv_rank`, dn / dr / dv = `latent_nope_dim` / `rope_dim` /
    `v_dim`:

        c_q = RMSNorm(x W_qa);  q = c_q W_qb, a head [q_nope (dn); q_rope (dr)]
        [c_kv (r); k_r (dr)] = x W_kva;  c_kv = RMSNorm(c_kv)
        q_rope = RoPE(q_rope);  k_rope = RoPE(k_r), ONE for all heads
        a head: k_nope = c_kv W_uk, v = c_kv W_uv, W_kvb = [W_uk; W_uv]
        s(i, j) = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) / sqrt(dn + dr)
        (times the layer's `RopeSpec.softmax_factor`, 1 without YaRN)
        o_i = sum_j softmax_j(s)(i, j) v_j;  y = concat(o) W_o

    What it keeps between calls is ONE row of r + dr values a token:
    `c_kv` after its norm and `k_rope` after its rotation. No K and V
    heads exist in the cache, so a cached call either makes them from the
    rows it reads or runs ABSORBED: a head's up-projections move onto the
    query and the output,

        qt = q_nope W_uk^T (r);  s(i, j) = (qt_i . c_kv_j + q_rope_i . k_rope_j) / sqrt(dn + dr)
        ot_i = sum_j p(i, j) c_kv_j (r);  o_i = ot_i W_uv

    the same numbers, with every head attending the one shared row. One
    token a row, `generate()`'s cache and the gather fallback run absorbed
    (2 r + dr values a pair and head, nothing a key); a prefill chunk that
    `ops.latent_chunk_attention` takes runs as written, the kernel
    up-projecting a key's heads in VMEM once a block of 512 queries (dn +
    dr + dv values a pair, 2 r (dn + dv) products a key: less from ~171
    queries a key up at the published widths), with no `absorb_q` /
    `absorb_out` around it.

    * With no cache (`decode=False`: training, the tests) the layer runs
      as first written: keys and values up-projected, dense causal softmax.
    * `decode=True` without tables is `generate()`'s cache: `latent`
      (B, max_seq_len, r + dr) and `index` in the "cache" collection.
    * With `block_tables` ((B, nb)) and `positions` ((B,)) `latent` is the
      serve engine's pool of blocks, (num_blocks, block_size, r + dr)
      (rows in whole lane tiles past 128: `ops.pool_latent_width`),
      shared by every row (`serve/cache.py`): writes scatter through the
      tables and an invalid entry drops them, as for K/V
      (`Attention._decode`). One token a row reads its pages in
      `ops.latent_decode_attention`, a prefill chunk in
      `ops.latent_chunk_attention`, where `ops.paged_kernel` says the
      kernel takes the pool; else the row's logical layout is gathered
      and a dense einsum masked by absolute position."""

    cfg: TransformerConfig
    spec: LayerSpec

    @nn.compact
    def __call__(
        self, x, cos, sin, decode: bool = False, positions=None,
        block_tables=None,
    ):
        cfg = self.cfg
        B, L, _ = x.shape
        H, r = self.spec.n_heads, cfg.latent_kv_rank
        dn, dr, dv = cfg.latent_nope_dim, cfg.latent_rope_dim, cfg.latent_v_dim
        scale = 1.0 / ((dn + dr) ** 0.5) * self.spec.rope.softmax_factor
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, name=name
        )
        with jax.named_scope("q_down"):
            c_q = RMSNorm(cfg.norm_eps, name="q_a_norm")(
                dense(cfg.latent_q_rank, "q_a_proj")(x)
            )
        with jax.named_scope("q_up"):
            q = dense(H * (dn + dr), "q_b_proj")(c_q).reshape(B, L, H, dn + dr)
            q_nope, q_rope = q[..., :dn], q[..., dn:]
        with jax.named_scope("kv_down"):
            kv = dense(r + dr, "kv_a_proj")(x)
            c_kv = RMSNorm(cfg.norm_eps, name="kv_a_norm")(kv[..., :r])
            k_rope = kv[..., None, r:]  # (B, L, 1, dr): every head's
        # a head's [W_uk (dn); W_uv (dv)], the columns of one (r, ...) matrix
        w_kvb = self.param(
            "kv_b_proj", nn.initializers.lecun_normal(), (r, H * (dn + dv))
        ).astype(cfg.dtype).reshape(r, H, dn + dv)
        w_uk, w_uv = w_kvb[..., :dn], w_kvb[..., dn:]

        if not decode:
            q_rope = apply_rope(q_rope, cos, sin)
            k_rope = apply_rope(k_rope, cos, sin)
            with jax.named_scope("kv_up"):
                k_nope = jnp.einsum("blr,rhd->blhd", c_kv, w_uk)
                v = jnp.einsum("blr,rhd->blhd", c_kv, w_uv)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (B, L, H, dr))], axis=-1
            )
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            o = _dense_attention(q, k, v, cfg.causal, scale)
            return dense(cfg.d_model, "o_proj")(o.reshape(B, L, H * dv))

        if not cfg.causal:
            raise ValueError("decode=True requires a causal model")
        if (block_tables is None) != (positions is None):
            raise ValueError(
                "a latent layer's paged cache takes block_tables and "
                "positions together; the dense cache keeps one scalar index"
            )
        M = cfg.max_seq_len
        if block_tables is None:
            cl = self.variable(
                "cache", "latent", jnp.zeros, (B, M, r + dr), cfg.dtype
            )
            ci = self.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
            idx = ci.value
            pos = jnp.broadcast_to(idx + jnp.arange(L), (B, L))
            pos_cos = jax.lax.dynamic_slice_in_dim(cos, idx, L, axis=0)
            pos_sin = jax.lax.dynamic_slice_in_dim(sin, idx, L, axis=0)
            q_rope = apply_rope(q_rope, pos_cos, pos_sin)
            k_rope = apply_rope(k_rope, pos_cos, pos_sin)
        else:
            if not self.has_variable("cache", "latent"):
                raise ValueError(
                    "a paged latent layer needs a pre-built block-pool cache "
                    "tree (serve.cache.init_paged_cache) passed via apply()"
                )
            cl = self.variable("cache", "latent", lambda: None)
            idx = positions.astype(jnp.int32)  # (B,) absolute start positions
            pos = idx[:, None] + jnp.arange(L)[None, :]  # (B, L) absolute
            safe = jnp.clip(pos, 0, M - 1)  # overshoot is dropped below
            q_rope = apply_rope_batched(q_rope, cos[safe], sin[safe])
            k_rope = apply_rope_batched(k_rope, cos[safe], sin[safe])
        row = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)  # (B, L, r + dr)

        def absorbed():
            with jax.named_scope("absorb_q"):
                qt = jnp.einsum("blhd,rhd->blhr", q_nope, w_uk)
                return jnp.concatenate([qt, q_rope], axis=-1)  # (B, L, H, r + dr)

        if block_tables is None:
            q = absorbed()
            with jax.named_scope("kv_scatter"):
                held = jax.lax.dynamic_update_slice_in_dim(cl.value, row, idx, axis=1)
            if not self.is_initializing():  # flax: init only creates
                cl.value, ci.value = held, idx + L
            with jax.named_scope("cache_attention"):
                mask = _position_mask(pos[0], jnp.arange(M))[None]
                ot = _latent_attention(q, held, r, scale, mask)
        else:
            kernel = self._write_rows(row, cl, pos, block_tables)
            if kernel == "latent_chunk":
                # a chunk's L queries pay for a key's heads: the layer as
                # written, over the pool, and no product outside the kernel
                from ..ops import latent_chunk_attention

                with jax.named_scope("cache_attention"):
                    o = latent_chunk_attention(
                        q_nope, q_rope, w_uk, w_uv, cl.value, block_tables,
                        idx, scale,
                    )
                return dense(cfg.d_model, "o_proj")(o.reshape(B, L, H * dv))
            ot = self._attend_pages(
                absorbed(), kernel, cl.value, pos, idx, block_tables, scale
            )
        with jax.named_scope("absorb_out"):
            o = jnp.einsum("blhr,rhd->blhd", ot, w_uv)
        return dense(cfg.d_model, "o_proj")(o.reshape(B, L, H * dv))

    def _write_rows(self, row, pool, pos, block_tables):
        """Write the call's rows (B, L, r + dr) at absolute positions pos
        (B, L) into the pool, and say which kernel of `ops.paged_kernel`
        takes the call's attention over it."""
        from ..ops import paged_kernel

        B, L, _ = row.shape
        nblk, bs, W = pool.value.shape
        flat = _paged_write_index(pos, block_tables, nblk, bs)
        with jax.named_scope("kv_scatter"):
            pool.value = pool.value.reshape(nblk * bs, W).at[flat].set(
                _grow(row, W).reshape(B * L, W), mode="drop"
            ).reshape(nblk, bs, W)
        return paged_kernel(
            L, pool.value, block_tables, rank=self.cfg.latent_kv_rank
        )

    def _attend_pages(self, q, kernel, pool, pos, idx, block_tables, scale):
        """Each row's pages in the ABSORBED form: q (B, L, H, r + dr), pos
        (B, L) absolute, `kernel` "latent_decode" or None (gather + einsum).
        Returns (B, L, H, r) in q's dtype."""
        from ..ops import gather_paged_latent, latent_decode_attention

        r = self.cfg.latent_kv_rank
        q = _grow(q, pool.shape[-1])
        with jax.named_scope("cache_attention"):
            if kernel == "latent_decode":
                return latent_decode_attention(
                    q[:, 0], pool, block_tables, idx, scale, rank=r
                )[:, None]
            with jax.named_scope("kv_gather"):
                held = gather_paged_latent(pool, block_tables)
            mask = _position_mask(pos, jnp.arange(held.shape[1])[None])
            return _latent_attention(q, held, r, scale, mask)


def _grow(a, width: int):
    """`a` with zeros behind its last axis up to `width`: a latent pool may
    hold wider rows than the model caches
    (`ops.paged_attention.pool_latent_width`), and zeros behind a row's
    values and behind a query's add nothing to a score."""
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, width - a.shape[-1])])


def _flash_ok(L: int, Dh: int, window: Optional[int] = None) -> bool:
    """Whether the flash kernel can take this call. A window layer never
    is (the kernel has no window yet) and says so once, like an
    untileable shape: L divisible by the
    EFFECTIVE block sizes (`resolved_block_sizes` fits env/table
    candidates so they tile L whenever possible) and head_dim within the
    kernel's VMEM tile. A `use_flash=True` model that lands on dense
    attention instead says so once per shape (the O(L^2) logits are a
    different memory and speed regime, not a detail)."""
    from ..ops.flash_attention import resolved_block_sizes

    if window is not None:
        import warnings

        warnings.warn(
            f"use_flash=True but the flash kernel has no window: this "
            f"window-{window} layer runs DENSE masked attention",
            RuntimeWarning,
            stacklevel=2,
        )
        return False
    bq, bk = resolved_block_sizes(L)
    ok = L % bq == 0 and L % bk == 0 and Dh <= 256
    if not ok:
        import warnings

        warnings.warn(
            f"use_flash=True but the flash kernel cannot take L={L}, "
            f"head_dim={Dh} (blocks {bq}x{bk}, head_dim limit 256): "
            "running DENSE attention for this shape",
            RuntimeWarning,
            stacklevel=2,
        )
    return ok


def linear_state_shapes(cfg) -> dict:
    """leaf -> (shape, dtype) of what ONE row keeps in a linear layer, as
    `LinearAttention` reads and writes it: the recurrent `state` and the
    pre-conv inputs behind the row's last token, `conv`."""
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    return {
        "state": ((H, dk, dv), jnp.float32),
        "conv": ((cfg.linear_conv - 1, H * (2 * dk + dv)), cfg.dtype),
    }


def conv_state_shapes(cfg) -> dict:
    """leaf -> (shape, dtype) of what ONE row keeps in a conv layer, as
    `GatedConv` reads and writes it: `tail`, the gated inputs (B * u) of
    the `conv_taps - 1` tokens behind the row's last."""
    return {"tail": ((cfg.conv_taps - 1, cfg.d_model), cfg.dtype)}


def layers_of(cfg) -> Tuple[str, ...]:
    """The kind of each layer (one of `CACHE_KINDS`), one a layer; a model
    configuration without a pattern keeps every key and value in all."""
    layers = getattr(cfg, "layers", None)
    if not layers:
        return ("full",) * cfg.n_layers
    return tuple(spec.attention for spec in layers)


def kv_shapes(cfg) -> dict:
    """leaf -> (shape, dtype) of what ONE token keeps in an attention layer,
    as `Attention` reads and writes it: its K and its V heads."""
    kv = ((cfg.kv_heads, cfg.head_dim), cfg.dtype)
    return {"k": kv, "v": kv}


def latent_shapes(cfg) -> dict:
    """leaf -> (shape, dtype) of what ONE token keeps in a latent layer, as
    `LatentAttention` reads and writes it: `latent`, the compressed latent
    and the shared rotary key in one row."""
    return {"latent": ((cfg.latent_width,), cfg.dtype)}


# kind -> (mixer, span, what one entry holds): the model's half of a kind's
# registration, in `CACHE_KINDS`' order
_CACHE_LEAVES = {
    "full": ("attn", "tokens", kv_shapes),
    "window": ("attn", "window", kv_shapes),
    "linear": ("linear_attn", "row", linear_state_shapes),
    "latent": ("latent_attn", "tokens", latent_shapes),
    "conv": ("gated_conv", "row", conv_state_shapes),
}
assert tuple(_CACHE_LEAVES) == CACHE_KINDS
# the kinds whose layers keep one state block a row and no keys
STATE_KINDS = tuple(
    kind for kind, (_, span, _) in _CACHE_LEAVES.items() if span == "row"
)


def cache_leaves(cfg, kind: str):
    """(mixer, span, leaves) of what a layer of `kind` (one of
    `CACHE_KINDS`) keeps between calls, in its mixer's own words: `mixer` is
    the name its subtree sits under in a block; `span` says how the state
    grows ("tokens": an entry a token, kept for ever; "window": an entry a
    token, of which the last `cfg.window` are read; "row": ONE entry a
    request, whatever its length); `leaves` is leaf -> (shape, dtype) of
    one entry. `models/generate.py` builds the dense cache and
    `serve/cache.py` the paged pool from this, without knowing which mixer
    it is; how a pool pads an entry is the pool's business
    (`serve/kinds.py`)."""
    mixer, span, shapes = _CACHE_LEAVES[kind]
    return mixer, span, shapes(cfg)


def state_block_shapes(cfg, kind: str):
    """(mixer, leaves) of `cache_leaves`, for a kind whose layers keep one
    state block a row (`STATE_KINDS`)."""
    mixer, _, leaves = cache_leaves(cfg, kind)
    return mixer, leaves


def _unit_lower_inverse(m):
    """Inverse of unit lower-triangular matrices (..., C, C), C a power of
    two, by blocks: T holds the inverses of the diagonal blocks of size s,
    and [[a, 0], [b, d]]^-1 = [[a', 0], [-d' b a', d']] gives those of size
    2s as T - T B T, B the lower-left quarters of m's diagonal blocks of
    size 2s. log2(C) rounds of two float32 products; no power of the matrix
    is ever formed (a run of equal keys makes those cancel by many orders
    of magnitude)."""
    C = m.shape[-1]
    rows, cols = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    hi = jax.lax.Precision.HIGHEST
    T = jnp.broadcast_to(jnp.eye(C, dtype=m.dtype), m.shape)
    s = 1
    while s < C:
        quarter = ((rows // s) % 2 == 1) & (cols // s == rows // s - 1)
        B = jnp.where(quarter, m, 0.0)
        T = T - jnp.matmul(jnp.matmul(T, B, precision=hi), T, precision=hi)
        s *= 2
    return T


def _channel_decayed_pairs(k, G, block: int):
    """`pairs(a)` = sum_c a_ic k_jc e^(G_ic - G_jc), (..., C, C), meant for
    j <= i only (the caller masks): what a decay a key channel leaves of a
    sub-chunk's dot products of `a` (its queries, or its keys) with its
    keys. a, k, G: (..., C, dk), G the running sum of log(alpha) <= 0 inside
    the sub-chunk; what depends on k and G alone is made once.

    The decay no longer leaves the dot product, and its factorised form
    (a e^G)(k e^-G)^T overflows float32 inside a sub-chunk once G passes
    -88, which the gate's initial range reaches in a few tokens. So the
    sub-chunk is cut into blocks of `block` tokens (the source's secondary
    chunking), and nothing is ever raised to a positive power:
    * i and j in one block: the sum over the channels written out, with
      e^(G_i - G_j) formed from the difference (<= 0 for j <= i);
    * j in an earlier block than i's: both sides measured from R, the sum
      up to the token before i's block: (a_i e^(G_i - R)) . (k_j e^(R -
      G_j)), two exponents <= 0 and a float32 product at `HIGH`. A factor
      underflows only where the whole term does."""
    *lead, C, dk = k.shape
    c0 = min(block, C)
    if C % c0:
        raise ValueError(f"a sub-chunk of {C} tokens in blocks of {c0}")
    n = C // c0
    blocks = lambda x: x.reshape(*lead, n, c0, dk)
    Gb = blocks(G)
    # (..., n, 1, dk): the running sum where each block starts
    R = jnp.concatenate(
        [jnp.zeros_like(Gb[..., :1, :1, :]), Gb[..., :-1, -1:, :]], axis=-3
    )
    since = jnp.exp(Gb - R)  # from its block's start to token i
    # token j's key as block I's start sees it, (..., n, C, dk)
    until = k[..., None, :, :] * jnp.exp(jnp.minimum(R - G[..., None, :, :], 0.0))
    # (..., n, c0, c0, dk): k_j e^(G_i - G_j), i and j in one block
    within = blocks(k)[..., None, :, :] * jnp.exp(
        jnp.minimum(Gb[..., :, None, :] - Gb[..., None, :, :], 0.0)
    )
    same = (jnp.arange(C)[:, None] // c0 == jnp.arange(C)[None, :] // c0).reshape(
        n, c0, n, c0
    )

    def pairs(a):
        ab = blocks(a)
        far = jnp.einsum(
            "...id,...jd->...ij", ab * since, until, precision=jax.lax.Precision.HIGH
        ).reshape(*lead, n, c0, n, c0)
        near = jnp.sum(ab[..., :, None, :] * within, axis=-1)  # (..., n, c0, c0)
        return jnp.where(same, near[..., :, :, None, :], far).reshape(*lead, C, C)

    return pairs


def gated_delta_chunked(q, k, v, g, beta, state, chunk: int):
    """The gated delta rule over L tokens, from `state`, in its chunked
    (WY) form. q, k: (B, L, H, dk), L2-normalised, q scaled; v: (B, L, H,
    dv); beta: (B, L, H); g = log(alpha) <= 0: (B, L, H), one decay a head,
    or (B, L, H, dk), one a key channel (a vector a head: state ROW c is
    decayed by alpha_c); state: (B, H, dk, dv); all float32. Returns (o (B,
    L, H, dv), the state after token L - 1).

    Per token S_t = Diag(alpha_t) S_{t-1} + beta_t k_t (v_t - (Diag(alpha_t)
    S_{t-1})^T k_t)^T, o_t = S_t^T q_t. Inside a sub-chunk of `chunk`
    tokens, with G the running sum of g, the rule's corrections solve a unit
    lower-triangular system (I + A) u = beta v - (beta k e^G) S_in, A_ij
    = beta_i sum_c k_ic k_jc e^(G_ic - G_jc) for j < i (with one decay a
    head: beta_i (k_i . k_j) e^(G_i - G_j); with a vector the decay stays
    inside the sum: `_channel_decayed_pairs`, in blocks of `LINEAR_BLOCK`
    tokens); the state moves sub-chunk by sub-chunk in a `lax.scan`.
    Everything is float32: the products at `Precision.HIGH`, the triangular
    inverse at `HIGHEST`, the rest elementwise. A position with g = 0 and
    beta = 0 (padding) leaves the state as it found it; L is padded up to
    whole sub-chunks with such positions."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    channel = g.ndim == 4
    pad = -L % C
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    N = (L + pad) // C
    # (B, H, N, C, ...)
    split = lambda a: jnp.moveaxis(a.reshape((B, N, C) + a.shape[2:]), 3, 1)
    q, k, v, g, beta = (split(a) for a in (q, k, v, g, beta))
    # float32 operands in three bfloat16 passes: at one pass (bfloat16
    # operands) the scan alone was a third of the error variance of a served
    # prefill against the float32 reference, which could then not be told
    # from a state kept in bfloat16 (PERF.md section 6, PR 31)
    mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGH)
    # the two forms in the order the scalar one was written in, so that a
    # model of one decay a head lowers to the text it lowered to before
    if channel:
        G = jnp.cumsum(g, axis=3)  # (B, H, N, C, dk)
        rows, cols = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
        kb, into = k * beta[..., None], jnp.exp(G)  # e^G: from S_in to token i
        pairs = _channel_decayed_pairs(k, G, LINEAR_BLOCK)
    else:
        G = jnp.cumsum(g, axis=-1)  # (B, H, N, C)
        rows, cols = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
        decay = jnp.exp(
            jnp.where(cols <= rows, G[..., :, None] - G[..., None, :], -jnp.inf)
        )  # e^(G_i - G_j) for j <= i, else 0
        kb, into = k * beta[..., None], jnp.exp(G)[..., None]
        pairs = lambda a: mm("bhnid,bhnjd->bhnij", a, k) * decay
    A = jnp.where(cols < rows, pairs(kb), 0.0)
    T = _unit_lower_inverse(A + jnp.eye(C, dtype=A.dtype))
    u = mm("bhnij,bhnjd->bhnid", T, v * beta[..., None])  # corrected values
    w = mm("bhnij,bhnjd->bhnid", T, kb * into)  # what S_in costs them
    qk = jnp.where(cols <= rows, pairs(q), 0.0)
    q_in = q * into
    if channel:
        last = G[..., -1:, :]  # (B, H, N, 1, dk)
        k_out, left = k * jnp.exp(last - G), jnp.exp(last)[..., 0, :]
    else:
        last = G[..., -1:]  # (B, H, N, 1)
        k_out, left = k * jnp.exp(last - G)[..., None], jnp.exp(last)

    def sub_chunk(S, xs):
        # left_i: what the sub-chunk leaves of S_in, (B, H, 1) or a state row's (B, H, dk)
        u_i, w_i, qk_i, q_i, k_i, left_i = xs
        new = u_i - mm("bhid,bhde->bhie", w_i, S)  # (B, H, C, dv)
        o = mm("bhid,bhde->bhie", q_i, S) + mm("bhij,bhje->bhie", qk_i, new)
        S = S * left_i[..., None] + mm("bhid,bhie->bhde", k_i, new)
        return S, o

    per_chunk = lambda a: jnp.moveaxis(a, 2, 0)  # N leads: what the scan walks
    S, o = jax.lax.scan(sub_chunk, state, tuple(
        per_chunk(a) for a in (u, w, qk, q_in, k_out, left)
    ))
    # (N, B, H, C, dv) -> (B, L, H, dv)
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(B, N * C, H, dv)
    return o[:, :L], S


class LinearAttention(nn.Module):
    """The "linear" mixer of a layer pattern: a gated delta rule in one of
    two forms. `cfg.linear_decay == "head"` is a Gated DeltaNet layer (Yang,
    Kautz, Hatamizadeh, arXiv:2412.06464). Per token, with H =
    `cfg.linear_heads` heads of key width dk and value width dv:

        q, k, v = silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))
            (a causal depthwise conv over time, `cfg.linear_conv` taps)
        per head: q <- q / |q| * dk^-1/2,  k <- k / |k|
        beta = (2 with `linear_neg_eigval`) * sigmoid(W_b x)
        g = -exp(A_log) * softplus(W_a x + dt_bias),  alpha = exp(g)
        S <- alpha S + beta k (v - alpha S^T k)^T     (dk x dv a head, float32)
        o = S^T q
        y = W_o [RMSNorm_dv(o) * w_norm * silu(W_g x)]

    `"channel"` is Kimi Delta Attention (arXiv:2510.26692): the decay is a
    VECTOR a head and token, one alpha a key channel, which decays the
    state's rows each by its own; `dt_bias` is one a channel (`A_log` still
    one a head); the output gate is a sigmoid; and the decay's and the
    gate's projections are low-rank pairs of `cfg.linear_gate_rank`, traced
    under the scope `kda_gate`:

        g = -exp(A_log) * softplus(W_f2 (W_f1 x) + dt_bias)    (H x dk)
        S <- Diag(alpha) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
        y = W_o [RMSNorm_dv(o) * w_norm * sigmoid(W_g2 (W_g1 x))]

    Everything the rule is computed from is float32: the projections of q,
    k, v and the gates keep their products' float32 (the rule multiplies a
    rounding of them by ten on its way to the logits; the second product
    of a low-rank pair takes its float32 input at `Precision.HIGH`), the
    conv, the norms and the state too; the mixer's input and output are
    `cfg.dtype`.

    What it keeps between calls is one recurrent state a row, `state`
    (H, dk, dv) float32, and the `linear_conv - 1` pre-conv inputs behind
    the row's last token, `conv` (taps - 1, H * (2 dk + dv)): no keys, no
    values, nothing that grows with the context.

    * With no cache (`decode=False`: training, the tests) the sequence
      runs from a zero state through `gated_delta_chunked`.
    * `decode=True` without tables is `generate()`'s cache: `state` and
      `conv` are (B, ...) variables of the "cache" collection.
    * With `block_tables` ((B, 1): each row's state block, `serve/
      cache.py`) and `positions` ((B,): where each row's first token
      stands) the variables are pools of blocks shared by every row. A
      row at position 0 reads a zero state and a zero conv tail whatever
      its block held; an invalid table entry (== the pool's blocks)
      drops the write, so a parked lane changes nothing. One token a row
      (the decode step) takes the recurrence itself: `ops.delta_recurrence.
      paged_delta_step`, a kernel that reads, updates and writes each live
      row's block in place (either form of the decay), where
      `delta_kernel_ok` says it takes the pool,
      else the same update in `jax.numpy` (`_delta_step` between a gather
      and a scatter). More than one token (a prefill chunk) takes the
      chunked form from the row's state.

    `row_mask` ((B, L) bool) marks real tokens; the others must be a
    row's trailing positions (the padding of a prefill chunk) and leave
    the state (alpha = 1 in every channel, beta = 0) and the conv tail
    (taken behind the last real token) as that token left them."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self, x, decode: bool = False, positions=None, block_tables=None,
        row_mask=None,
    ):
        cfg = self.cfg
        B, L, _ = x.shape
        H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
        taps, width = cfg.linear_conv, H * (2 * dk + dv)
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, name=name
        )
        # what the recurrence is computed from keeps the products' float32
        # (bfloat16 operands, no rounding of the result): the rule turns a
        # rounding of q, k, v or a gate into ten times as much in the logits
        wide = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, name=name,
            dot_general=functools.partial(
                jax.lax.dot_general, preferred_element_type=jnp.float32
            ),
        )
        f32 = jnp.float32
        qkv = jnp.concatenate(
            [wide(H * dk, "q_proj")(x), wide(H * dk, "k_proj")(x),
             wide(H * dv, "v_proj")(x)], axis=-1,
        ).astype(f32)  # (B, L, width), before the conv
        beta = jax.nn.sigmoid(wide(H, "b_proj")(x).astype(f32))
        if cfg.linear_neg_eigval:
            beta = 2.0 * beta
        channel = cfg.linear_decay == "channel"

        def low_rank(feats, name):
            """x through the pair `name`_a, `name`_b, the float32 in between
            kept."""
            inner = wide(cfg.linear_gate_rank, f"{name}_a")(x).astype(f32)
            return nn.Dense(
                feats, use_bias=False, dtype=f32, name=f"{name}_b",
                precision=jax.lax.Precision.HIGH,
            )(inner)

        a_log = self.param("A_log", _a_log_init, (H,))
        if channel:
            dt_bias = self.param("dt_bias", _dt_bias_init, (H, dk))
            with jax.named_scope("kda_gate"):
                g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
                    low_rank(H * dk, "f_proj").reshape(B, L, H, dk)
                    + dt_bias.astype(f32)
                )  # log(alpha), (B, L, H, dk)
                out_gate = jax.nn.sigmoid(
                    low_rank(H * dv, "g_proj").reshape(B, L, H, dv)
                )
        else:
            dt_bias = self.param("dt_bias", _dt_bias_init, (H,))
            g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                wide(H, "a_proj")(x).astype(f32) + dt_bias.astype(f32)
            )  # log(alpha), (B, L, H)
        real = L
        if row_mask is not None:
            beta = jnp.where(row_mask[..., None], beta, 0.0)
            g = jnp.where(row_mask[(...,) + (None,) * (g.ndim - 2)], g, 0.0)
            real = jnp.sum(row_mask, axis=1).astype(jnp.int32)  # (B,)

        # -- the state this call starts from, and where it leaves it ------
        paged = block_tables is not None
        if paged and (positions is None or not self.has_variable("cache", "state")):
            raise ValueError(
                "a paged linear layer needs positions and a pre-built state "
                "pool (serve.cache.init_paged_cache) passed via apply()"
            )
        state = jnp.zeros((B, H, dk, dv), f32)
        tail = jnp.zeros((B, taps - 1, width), qkv.dtype)
        if decode:
            cs, cc = (
                self.variable("cache", leaf, jnp.zeros, (B,) + shape, dtype)
                for leaf, (shape, dtype) in linear_state_shapes(cfg).items()
            )
        scope = "recurrence" if decode and L == 1 else "chunk_scan"
        kernel = False
        if paged:
            from ..ops.delta_recurrence import delta_kernel_ok, paged_delta_step

            block = block_tables[:, 0]  # (B,), == the pool's blocks: none
            fresh = positions == 0
            # one token a row over a pool the kernel takes: it reads and
            # writes the rows' state blocks itself, in place
            kernel = scope == "recurrence" and delta_kernel_ok(cs.value)
            with jax.named_scope(scope):  # the row's block, read once
                at = lambda pool: jnp.take(pool, block, axis=0, mode="clip")
                tail = jnp.where(fresh[:, None, None], 0, at(cc.value)).astype(
                    qkv.dtype
                )
                if not kernel:
                    state = jnp.where(fresh[:, None, None, None], 0.0, at(cs.value))
        elif decode:
            state, tail = cs.value, cc.value.astype(qkv.dtype)

        with jax.named_scope("short_conv"):
            seq = jnp.concatenate([tail, qkv], axis=1)  # (B, taps - 1 + L, width)
            taps_weight = self.param(
                "conv", nn.initializers.lecun_normal(in_axis=0, out_axis=1),
                (taps, width),
            ).astype(f32)
            qkv = jax.nn.silu(
                sum(taps_weight[i] * seq[:, i:i + L] for i in range(taps))
            )
            if row_mask is None:
                tail = seq[:, L:]
            else:  # behind each row's last real token
                tail = jax.vmap(
                    lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, taps - 1)
                )(seq, real)
        heads = lambda a, d: a.reshape(B, L, H, d)
        q = heads(qkv[..., : H * dk], dk)
        k = heads(qkv[..., H * dk: 2 * H * dk], dk)
        v = heads(qkv[..., 2 * H * dk:], dv)
        unit = lambda a: a * jax.lax.rsqrt(
            jnp.maximum(jnp.sum(a * a, axis=-1, keepdims=True), 1e-12)
        )
        q, k = unit(q) * dk ** -0.5, unit(k)

        with jax.named_scope(scope):
            if scope == "recurrence":
                step = (q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0])
                if kernel:
                    o, cs.value = paged_delta_step(cs.value, block, fresh, *step)
                else:
                    o, state = _delta_step(*step, state)
                o = o[:, None]
            else:
                o, state = gated_delta_chunked(
                    q, k, v, g, beta, state, LINEAR_CHUNK
                )
            # and written once; during init the variables are only created
            # (flax's convention)
            if paged:
                if not kernel:
                    cs.value = cs.value.at[block].set(state, mode="drop")
                cc.value = cc.value.at[block].set(tail.astype(cfg.dtype), mode="drop")
            elif decode and not self.is_initializing():
                cs.value, cc.value = state, tail.astype(cfg.dtype)

        with jax.named_scope("gated_norm"):
            w_norm = self.param("norm", nn.initializers.ones, (dv,))
            var = jnp.mean(o * o, axis=-1, keepdims=True)
            o = o * jax.lax.rsqrt(var + cfg.norm_eps) * w_norm.astype(f32)
            if not channel:
                out_gate = jax.nn.silu(
                    wide(H * dv, "g_proj")(x).astype(f32).reshape(B, L, H, dv)
                )
            o = (o * out_gate).astype(cfg.dtype).reshape(B, L, H * dv)
        return dense(cfg.d_model, "o_proj")(o)


def _delta_step(q, k, v, alpha, beta, S):
    """One token of the gated delta rule for every row: q, k (B, H, dk),
    v (B, H, dv), beta (B, H), alpha (B, H), one decay a head, or (B, H,
    dk), one a state ROW; S (B, H, dk, dv), float32. Returns (o (B, H,
    dv), the new S). Both (alpha S)^T k and (alpha S)^T q come from the one
    read of S, as S^T (alpha k) and S^T (alpha q) (o = (alpha S)^T q + (k .
    q) d, d the rule's correction), the update is the second and only other
    pass: elementwise and reductions, nothing rounds the state."""
    a = alpha if alpha.ndim == k.ndim else alpha[..., None]  # a state row's decay
    Sk = jnp.sum(S * (a * k)[..., None], axis=-2)
    Sq = jnp.sum(S * (a * q)[..., None], axis=-2)
    d = beta[..., None] * (v - Sk)  # (B, H, dv)
    kq = jnp.sum(k * q, axis=-1, keepdims=True)
    new = a[..., None] * S + k[..., None] * d[..., None, :]
    return Sq + kq * d, new


def _a_log_init(key, shape, dtype=jnp.float32):
    """log of a decay rate drawn from (0, 16), as the layer's source does."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 2.0 ** -10, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniformly from (1e-3, 1e-1)."""
    import math

    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class GatedConv(nn.Module):
    """The "conv" mixer of a layer pattern: a gated short convolution
    (the LFM2 family's operator). Per token, with D = `cfg.d_model` and
    T = `cfg.conv_taps`:

        [B, C, u] = split3(W_in x)            (W_in: D -> 3 D, no bias)
        z_t = sum_{j < T} w_j * (B * u)_{t - (T - 1) + j}   (depthwise, causal)
        y = W_out (C * z)

    No activation inside, no bias. The three elementwise products and the
    taps are float32 (`in_proj` keeps its product's float32; `out_proj`
    takes `cfg.dtype`); the mixer's input and output are `cfg.dtype`.

    What it keeps between calls is `tail`, the gated inputs B * u of the
    T - 1 tokens behind the row's last, (T - 1, D) in `cfg.dtype`: no keys,
    no values, nothing that grows with the context.

    * With no cache (`decode=False`: training, the tests) the sequence
      runs from a zero tail.
    * `decode=True` without tables is `generate()`'s cache: `tail` is a
      (B, T - 1, D) variable of the "cache" collection.
    * With `block_tables` ((B, 1): each row's state block, `serve/
      cache.py`) and `positions` ((B,): where each row's first token
      stands) `tail` is a pool of blocks shared by every row. A row at
      position 0 reads a zero tail whatever its block held; an invalid
      table entry (== the pool's blocks) drops the write, so a parked lane
      changes nothing. One token a row is the decode step (scope
      `conv_step`: tail read, the taps, tail write), more a prefill chunk
      (`conv_chunk`).

    `row_mask` ((B, L) bool) marks real tokens; the others must be a
    row's trailing positions (the padding of a prefill chunk): they enter
    no real token's sum (they stand behind every real one) and the tail
    is taken behind the last REAL token."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self, x, decode: bool = False, positions=None, block_tables=None,
        row_mask=None,
    ):
        cfg = self.cfg
        B, L, D = x.shape
        taps, f32 = cfg.conv_taps, jnp.float32
        paged = block_tables is not None
        if paged and (positions is None or not self.has_variable("cache", "tail")):
            raise ValueError(
                "a paged conv layer needs positions and a pre-built state "
                "pool (serve.cache.init_paged_cache) passed via apply()"
            )
        bcu = nn.Dense(
            3 * D, use_bias=False, dtype=cfg.dtype, name="in_proj",
            dot_general=functools.partial(
                jax.lax.dot_general, preferred_element_type=f32
            ),
        )(x).astype(f32)
        weight = self.param(
            "conv", nn.initializers.lecun_normal(in_axis=0, out_axis=1), (taps, D)
        ).astype(f32)
        if decode:
            (shape, dtype), = conv_state_shapes(cfg).values()
            ct = self.variable("cache", "tail", jnp.zeros, (B,) + shape, dtype)
        with jax.named_scope("conv_step" if decode and L == 1 else "conv_chunk"):
            gate, c, u = jnp.split(bcu, 3, axis=-1)
            bu = gate * u  # (B, L, D)
            if paged:
                block = block_tables[:, 0]  # (B,), == the pool's blocks: none
                tail = jnp.where(
                    (positions == 0)[:, None, None], 0,
                    jnp.take(ct.value, block, axis=0, mode="clip"),
                ).astype(f32)
            elif decode:
                tail = ct.value.astype(f32)
            else:
                tail = jnp.zeros((B, taps - 1, D), f32)
            seq = jnp.concatenate([tail, bu], axis=1)  # (B, taps - 1 + L, D)
            z = sum(weight[i] * seq[:, i:i + L] for i in range(taps))
            if decode:
                if row_mask is None or L == 1:
                    tail = seq[:, L:]
                else:  # behind each row's last real token
                    real = jnp.sum(row_mask, axis=1).astype(jnp.int32)
                    tail = jax.vmap(
                        lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, taps - 1)
                    )(seq, real)
                tail = tail.astype(cfg.dtype)
                # during init the variable is only created (flax's convention)
                if paged:
                    ct.value = ct.value.at[block].set(tail, mode="drop")
                elif not self.is_initializing():
                    ct.value = tail
            mixed = (c * z).astype(cfg.dtype)
        return nn.Dense(D, use_bias=False, dtype=cfg.dtype, name="out_proj")(mixed)


class MLP(nn.Module):
    cfg: TransformerConfig
    width: Optional[int] = None  # None: cfg.ffn_dim

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        F = self.width or cfg.ffn_dim
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, name=name
        )
        gate = checkpoint_name(dense(F, "gate_proj")(x), _remat.MLP_GATE)
        up = checkpoint_name(dense(F, "up_proj")(x), _remat.MLP_UP)
        return dense(cfg.d_model, "down_proj")(nn.silu(gate) * up)


class MoE(nn.Module):
    """Top-k MoE MLP (k=1 Switch, k>1 GShard/Mixtral) — experts shardable
    over an ``ep`` mesh axis
    via `sharding_rules(ep_axis=...)`; routing math in
    parallel/expert_parallel.moe_mlp (axis-free form here: under jit,
    GSPMD partitions the expert einsums from the param shardings).
    The load-balance aux loss is sown as intermediates/moe_aux."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from ..parallel.expert_parallel import moe_mlp

        cfg = self.cfg
        B, L, D = x.shape
        E, F = cfg.n_experts, cfg.ffn_dim
        init = nn.initializers.lecun_normal()
        w_up = self.param("experts_up", init, (E, D, F))
        w_down = self.param("experts_down", init, (E, F, D))
        router = self.param("router", init, (D, E))
        y, aux = moe_mlp(
            x.reshape(B * L, D).astype(cfg.dtype),
            w_up.astype(cfg.dtype),
            w_down.astype(cfg.dtype),
            router,
            axis_name=None,
            capacity_factor=cfg.moe_capacity_factor,
            k=cfg.moe_top_k,
        )
        self.sow("intermediates", "moe_aux", aux)
        return y.reshape(B, L, D)


class SparseMoE(nn.Module):
    """The "sparse" MLP of a layer pattern: dropless top-k routing over
    `cfg.sparse_experts` SwiGLU experts (`parallel/expert_parallel.py::
    dropless_moe`: every assignment is computed, none dropped, no
    capacity), plus one shared SwiGLU expert added unweighted. Experts
    are stacked (held, D, F) x 2 and (held, F, D), `held` the contiguous
    range `cfg.experts_held` gives this chip (all of them by default);
    the router keeps its full width. With `cfg.sparse_choice_bias` the
    layer owns `router_bias` (E,), added to the scores for the CHOICE of
    the top k and not for their weights.

    `row_mask` ((B, L) bool) marks the rows that are real tokens: the
    others (a parked lane of the serve step, the padding of a prefill
    chunk) route nowhere and count nowhere. Per call the layer sows
    `intermediates/moe_stats`: int32 (assignments computed here,
    distinct experts here with at least one row), and `moe_chosen`, the
    (B, L, top_k) experts the router picked."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, row_mask=None):
        from ..parallel.expert_parallel import dropless_moe

        cfg = self.cfg
        B, L, D = x.shape
        E, F = cfg.sparse_experts, cfg.sparse_d_ff
        first, held = cfg.experts_held or (0, E)
        # fan-in of ONE expert, whatever the stack holds
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(), (D, E))
        w_gate = self.param("experts_gate", init, (held, D, F))
        w_up = self.param("experts_up", init, (held, D, F))
        w_down = self.param("experts_down", init, (held, F, D))
        bias = None
        if cfg.sparse_choice_bias:
            # N(0, 0.05): beside sigmoid scores of seeded weights it changes
            # a few per cent of the choices
            bias = self.param("router_bias", nn.initializers.normal(0.05), (E,))
        with jax.named_scope("moe"):
            y, stats, chosen = dropless_moe(
                x.reshape(B * L, D).astype(cfg.dtype), router,
                w_gate.astype(cfg.dtype), w_up.astype(cfg.dtype),
                w_down.astype(cfg.dtype),
                n_experts=E, top_k=cfg.sparse_top_k, scale=cfg.routed_scale,
                first_expert=first, score=cfg.sparse_score,
                row_mask=None if row_mask is None else row_mask.reshape(B * L),
                choice_bias=bias, norm_eps=cfg.sparse_norm_eps,
            )
            self.sow("intermediates", "moe_stats", stats)
            self.sow("intermediates", "moe_chosen", chosen.reshape(B, L, -1))
            y = y.reshape(B, L, D)
            if cfg.shared_d_ff:
                y = y + MLP(cfg, cfg.shared_d_ff, name="shared_expert")(x)
        return y


class HyperConnection(nn.Module):
    """The maps of one sublayer's residual streams: manifold-constrained
    hyper-connections (mHC, arXiv:2512.24880, on Hyper-Connections,
    arXiv:2409.19606). A token's state X is (n, C), n = `cfg.hc_mult`; the
    model carries the streams as the LEADING axis, (n, B, L, C), so that a
    stream is a contiguous plane. All in float32, whatever `cfg.dtype`:

        x = vec(X) (nC);  rho = (mean(x^2) + norm_eps)^-1/2;  m = rho * (x Phi)
        h_pre  = sigmoid(a1 m[0:n] + b[0:n]) + hc_eps           (n)
        h_post = 2 sigmoid(a2 m[n:2n] + b[n:2n])                (n)
        A = clip(a3 m[2n:] + b[2n:], hc_clamp) as n x n;  M = exp(A);
        `hc_sinkhorn_iters` times: M /= rowsum(M) + hc_eps, then
        M /= colsum(M) + hc_eps;  H_res = M  (doubly stochastic)
        pre:  u = sum_i h_pre[i] X[i]  (C, in X's dtype): the sublayer's input
        post: X'[i] = h_post[i] y + sum_j H_res[i, j] X[j]

    Phi (nC, 2n + n^2) ~ N(0, 1/(nC)), a = 1, b = 0: m is O(1) and every
    map depends on the token. With `collapse` the module is the END of the
    streams: Phi (nC, n), one a, and `pre` gives x_out = sum_i h_out[i] X[i]
    with h_out as h_pre is; nothing is written back.

    A map is a function of its own token's streams and of nothing else: a
    chunk's padding and a step's parked rows pass through and touch no
    real row."""

    cfg: TransformerConfig
    collapse: bool = False

    @nn.compact
    def pre(self, X):
        """X (n, ..., C), the streams LEADING -> (u (..., C), h_post
        (n, ...), H_res (n, n, ...)); with `collapse` the last two are
        None."""
        cfg = self.cfg
        n, C = X.shape[0], X.shape[-1]
        lead = X.shape[1:-1]
        kinds = 1 if self.collapse else 3
        width = n if self.collapse else 2 * n + n * n
        phi = self.param(
            "phi", nn.initializers.normal((n * C) ** -0.5), (n * C, width)
        )
        alpha = self.param("alpha", nn.initializers.ones, (kinds,))
        bias = self.param("bias", nn.initializers.zeros, (width,))
        with jax.named_scope("hc_pre"):
            x = X.reshape(n, -1, C)  # (n, T, C)
            with jax.named_scope("hc_mix"):
                x32 = x.astype(jnp.float32)
                rho = jax.lax.rsqrt(
                    jnp.sum(x32 * x32, axis=(0, 2)) / (n * C) + cfg.norm_eps
                )  # (T,)
                m = rho[:, None] * self._product(
                    x, phi.astype(jnp.float32).reshape(n, C, width)
                )
                a = jnp.concatenate([
                    jnp.broadcast_to(alpha[k].astype(jnp.float32), (size,))
                    for k, size in enumerate((n, n, n * n)[:kinds])
                ])
                # tokens last: the maps are a few values a token, and the
                # chain below is then elementwise over whole rows of tokens
                m = (a * m + bias.astype(jnp.float32)).T  # (width, T)
            h_pre = jax.nn.sigmoid(m[:n]) + cfg.hc_eps
            # a weighted sum of n streams, elementwise: no product of 4s
            u = sum(
                h_pre[i][:, None] * x[i].astype(jnp.float32) for i in range(n)
            ).astype(X.dtype).reshape(*lead, C)
            if self.collapse:
                return u, None, None
            h_post = 2.0 * jax.nn.sigmoid(m[n:2 * n])
            with jax.named_scope("hc_sinkhorn"):
                A = jnp.exp(jnp.clip(m[2 * n:], *cfg.hc_clamp))
                h_res = self._sinkhorn(
                    tuple(A[k] for k in range(n * n)), n, cfg.hc_sinkhorn_iters,
                    cfg.hc_eps,
                ).reshape(n, n, -1)
        return u, h_post.reshape(n, *lead), h_res.reshape(n, n, *lead)

    @staticmethod
    def _sinkhorn(M, n, iters, eps):
        """`iters` times: rows of the n x n matrix over their sums, then
        columns over theirs. M is n * n rows of tokens, and every sum is
        written out, so a normalisation is elementwise over whole rows of
        tokens and the compiler fuses a run of them into one operation
        (a `sum` over an axis makes each a small reduction of its own: 80
        operations a map at a microsecond or two each, a twelfth of a
        decode step). `SINKHORN_UNROLL` normalisation pairs are written
        out a trip of a loop: all of them in one body is five times the
        program to compile for a few microseconds a map."""
        def pairs(M, count):
            M = list(M)
            for _ in range(count):
                for axis in (0, 1):  # rows, then columns
                    lines = [
                        [i * n + j if axis == 0 else j * n + i for j in range(n)]
                        for i in range(n)
                    ]
                    for line in lines:
                        total = sum(M[k] for k in line) + eps
                        for k in line:
                            M[k] = M[k] / total
            return tuple(M)

        trips, rest = divmod(iters, SINKHORN_UNROLL)
        M = jax.lax.fori_loop(
            0, trips, lambda _, M: pairs(M, SINKHORN_UNROLL), tuple(M)
        )
        return jnp.stack(pairs(M, rest))

    @staticmethod
    def _product(x, phi):
        """x (n, T, C) against the float32 phi (n, C, width), contracted
        over streams and features, at float32's precision. A bfloat16 x is
        exact in three bfloat16 pieces of phi (high, middle and low bits,
        side by side in ONE product that accumulates in float32): the
        streams are read as they are held, not from a float32 copy."""
        if x.dtype != jnp.bfloat16:
            return jnp.einsum(
                "ntc,ncw->tw", x.astype(jnp.float32), phi,
                precision=jax.lax.Precision.HIGHEST,
            )
        pieces, rest = [], phi
        for _ in range(3):
            pieces.append(rest.astype(jnp.bfloat16))
            rest = rest - pieces[-1].astype(jnp.float32)
        wide = jnp.einsum(
            "ntc,ncw->tw", x, jnp.concatenate(pieces, axis=-1),
            preferred_element_type=jnp.float32,
        )
        return sum(jnp.split(wide, 3, axis=1))

    @staticmethod
    def post(X, y, h_post, h_res):
        """The sublayer's output y (..., C) written back into the remixed
        streams X (n, ..., C), in X's dtype."""
        with jax.named_scope("hc_post"):
            out = h_post[..., None] * y.astype(jnp.float32)[None]
            for j in range(X.shape[0]):
                out = out + h_res[:, j][..., None] * X[j].astype(jnp.float32)[None]
            return out.astype(X.dtype)


class Block(nn.Module):
    cfg: TransformerConfig
    spec: Optional[LayerSpec] = None  # `cfg.layer(i)`; None: no pattern

    @nn.compact
    def __call__(
        self, x, cos, sin, decode: bool = False, positions=None,
        block_tables=None, row_mask=None,
    ):
        cfg = self.cfg
        # where the two norms stand: before each sublayer, or (a pattern
        # with `post_norm`) on its output, before the residual add, or
        # (`sandwich_norm`) on both: `attn_norm` / `mlp_norm` on the input,
        # `attn_post_norm` / `mlp_post_norm` on the output
        post = self.spec is not None and cfg.post_norm
        sandwich = self.spec is not None and cfg.sandwich_norm
        norm_in = lambda name, h: h if post else RMSNorm(cfg.norm_eps, name=name)(h)

        def norm_out(name, y):
            if sandwich:
                name = name.replace("_norm", "_post_norm")
            return RMSNorm(cfg.norm_eps, name=name)(y) if post or sandwich else y

        def residual(name, x, sublayer):
            """x + sublayer(x); with residual streams the sublayer reads a
            mixture of them and its output is written back through the
            maps of `HyperConnection`."""
            if cfg.hc_mult == 1:
                return x + sublayer(x)
            hc = HyperConnection(cfg, name=name)
            u, h_post, h_res = hc.pre(x)
            return hc.post(x, sublayer(u), h_post, h_res)

        def attention(u):
            h = norm_in("attn_norm", u)
            if self.spec is not None and self.spec.attention == "linear":
                mixed = LinearAttention(cfg, name="linear_attn")(
                    h, decode, positions, block_tables, row_mask
                )
            elif self.spec is not None and self.spec.attention == "conv":
                mixed = GatedConv(cfg, name="gated_conv")(
                    h, decode, positions, block_tables, row_mask
                )
            elif self.spec is not None and self.spec.attention == "latent":
                mixed = LatentAttention(cfg, self.spec, name="latent_attn")(
                    h, cos, sin, decode, positions, block_tables
                )
            else:
                mixed = Attention(cfg, self.spec, name="attn")(
                    h, cos, sin, decode, positions, block_tables
                )
            return norm_out("attn_norm", mixed)

        def mlp(u):
            h = norm_in("mlp_norm", u)
            if self.spec is not None and self.spec.mlp == "sparse":
                return norm_out("mlp_norm", SparseMoE(cfg, name="mlp")(h, row_mask))
            mlp_cls = MoE if cfg.n_experts > 0 else MLP
            return norm_out("mlp_norm", mlp_cls(cfg, name="mlp")(h))

        x = residual("hc_attn", x, attention)
        x = checkpoint_name(x, _remat.BLOCK_MID)
        return residual("hc_mlp", x, mlp)


def _remat_block():
    """`Block` under `jax.checkpoint`, keeping the rung of
    `utils/remat.LADDER` the trainer's step is being traced at; with no
    trainer around the trace, the plain `nn.remat(Block)`."""
    return nn.remat(Block, policy=_remat.save_policy(_remat.rung()))


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self, tokens, decode: bool = False, positions=None,
        block_tables=None, row_mask=None,
    ):
        """tokens: (B, L) int32 → logits (B, L, vocab) fp32.

        `decode=True` switches attention to the KV-cache path (flax
        "cache" collection; apply with `mutable=["cache"]`): call once
        with the prompt (prefill), then with one token at a time —
        `models/generate.py` wraps the loop. `block_tables` ((B, nb)
        int32) with `positions` ((B,) int32) switches the cache to the
        serve engine's PAGED block pool (`serve/cache.py`): one
        (num_blocks, block_size, kv_heads, head_dim) K/V pool per layer
        shared by all rows, indexed through per-row block tables, each
        row advancing from its own depth `positions[b]`.

        A model with a layer pattern (`cfg.layers`) builds each block
        from its `LayerSpec` and hands it the rope table of its own
        `RopeSpec`. Where the layers keep more than one kind of state
        (`cfg.cache_kinds`: every key and value, a window of them, a
        state block, a latent row), `block_tables` is the TUPLE of `serve/cache.py`'s
        tables, one a kind in that order, and each layer takes its kind's.
        `row_mask` ((B, L) bool, optional) marks the rows that are real
        tokens, for the sparse MLPs (see `SparseMoE`) and the mixers that
        keep a state block (see `LinearAttention`, `GatedConv`)."""
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="tok_embed"
        )
        x = embed(tokens)
        rope_len = cfg.max_seq_len if decode else tokens.shape[1]
        if cfg.layers is not None:
            return self._patterned(
                x, rope_len, decode, positions, block_tables, row_mask,
                embed if cfg.tie_embeddings else None,
            )
        if cfg.tie_embeddings:
            raise ValueError("tie_embeddings belongs to a layer pattern")
        cos, sin = rope_freqs(cfg.head_dim, rope_len, cfg.rope_theta)
        # remat path: `decode` must NOT flow through nn.remat as a traced
        # positional (TracerBoolConversionError at `if decode:`); the
        # rematted path is always decode=False, so rely on the default
        use_remat = cfg.remat and not decode
        block_cls = _remat_block() if use_remat else Block
        for i in range(cfg.n_layers):
            if use_remat:
                x = block_cls(cfg, name=f"layers_{i}")(x, cos, sin)
            else:
                x = block_cls(cfg, name=f"layers_{i}")(
                    x, cos, sin, decode, positions, block_tables
                )
        return self._head(x)

    @nn.nowrap
    def _head(self, x, embed=None):
        """Final norm, then the logits: against `lm_head`, or (`embed`: a
        tied model's `tok_embed`) against the embedding's own array."""
        cfg = self.cfg
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        if embed is not None:
            with jax.named_scope("lm_head"):
                return embed.attend(x).astype(jnp.float32)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype, name="lm_head"
        )(x)
        return logits.astype(jnp.float32)

    @nn.nowrap
    def _patterned(
        self, x, rope_len, decode, positions, block_tables, row_mask, embed=None,
    ):
        """The blocks of a model with a layer pattern, then the head
        (`embed`: see `_head`)."""
        cfg = self.cfg
        specs = [cfg.layer(i) for i in range(cfg.n_layers)]
        latent = lambda spec: spec.attention == "latent"
        tables = {
            rope: rope_table(rope, cfg.head_dim, rope_len)
            for rope in {spec.rope for spec in specs if not latent(spec)}
        }
        # a latent layer rotates its `latent_rope_dim` values, not a head's
        latent_tables = {
            rope: rope_table(rope, cfg.latent_rope_dim, rope_len)
            for rope in {spec.rope for spec in specs if latent(spec)}
        }
        paired = isinstance(block_tables, (tuple, list))
        kinds = cfg.cache_kinds
        use_remat = cfg.remat and not decode
        block_cls = _remat_block() if use_remat else Block
        if cfg.hc_mult > 1:  # the streams start as copies of the embedding
            x = jnp.broadcast_to(x[None], (cfg.hc_mult, *x.shape))
        for i, spec in enumerate(specs):
            cos, sin = (latent_tables if latent(spec) else tables)[spec.rope]
            block = block_cls(cfg, spec, name=f"layers_{i}")
            if use_remat:  # see __call__: `decode` stays a Python default
                x = block(x, cos, sin)
                continue
            bt = block_tables
            if paired:
                bt = block_tables[kinds.index(spec.attention)]
            x = block(x, cos, sin, decode, positions, bt, row_mask)
        if cfg.hc_mult > 1:  # and end in a learned mixture of the four
            x, _, _ = HyperConnection(cfg, collapse=True, name="hc_out").pre(x)
        return self._head(x, embed)


def sharding_rules(
    tp_axis: str = "tp",
    fsdp_axis: Optional[str] = "fsdp",
    ep_axis: Optional[str] = None,
) -> Sequence[Tuple[str, Tuple]]:
    """Canonical 2-D GSPMD layout for TransformerLM params.

    Megatron pairing: q/k/v/gate/up colwise over ``tp``; o/down rowwise
    over ``tp``; ZeRO dimension over ``fsdp`` on the complementary dim.
    MoE expert stacks shard dim 0 over ``ep_axis`` (falls back to
    ``fsdp_axis``). Set ``fsdp_axis=None`` for pure TP.
    """
    f = fsdp_axis
    e = ep_axis or fsdp_axis
    return [
        (r"tok_embed/embedding", (None, tp_axis)),
        (r"(q_proj|k_proj|v_proj)/kernel", (f, tp_axis)),
        (r"o_proj/kernel", (tp_axis, f)),
        (r"(gate_proj|up_proj)/kernel", (f, tp_axis)),
        (r"down_proj/kernel", (tp_axis, f)),
        (r"experts_up", (e, None, tp_axis)),
        (r"experts_down", (e, tp_axis, None)),
        (r"router", ()),
        (r"lm_head/kernel", (f, tp_axis)),
        (r"(attn_norm|mlp_norm|final_norm)/scale", (None,)),
        (r".*", ()),
    ]
