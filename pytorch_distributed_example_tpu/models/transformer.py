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
* `nn.remat` per block when `remat=True` (HBM ↔ FLOPs trade, SURVEY task
  note on `jax.checkpoint`).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


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
    remat: bool = False
    n_experts: int = 0  # > 0 switches the MLP to a top-k MoE
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1  # 1 = Switch, 2 = GShard/Mixtral-style

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

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


def apply_rope(x, cos, sin):
    """x: (B, L, H, D); rotate pairs (even, odd) by position angle."""
    with jax.named_scope("rope"):
        x1, x2 = x[..., 0::2], x[..., 1::2]
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
        r1 = x1 * c - x2 * s
        r2 = x2 * c + x1 * s
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
        return out.astype(x.dtype)


def apply_rope_batched(x, cos, sin):
    """x: (B, L, H, D); cos/sin: (B, L, D/2) — per-SAMPLE position
    angles, for decode batches where every row sits at its own absolute
    position (the serve engine's slot batch)."""
    with jax.named_scope("rope"):
        x1, x2 = x[..., 0::2], x[..., 1::2]
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
        r1 = x1 * c - x2 * s
        r2 = x2 * c + x1 * s
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
        return out.astype(x.dtype)


def _dense_attention(q, k, v, causal, scale):
    from ..ops.reference import dense_attention

    with jax.named_scope("dense_attention"):
        return dense_attention(q, k, v, causal=causal, scale=scale)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self, x, cos, sin, decode: bool = False, positions=None,
        block_tables=None,
    ):
        cfg = self.cfg
        B, L, _ = x.shape
        H, KV, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, name=name
        )
        q = dense(H * Dh, "q_proj")(x).reshape(B, L, H, Dh)
        k = dense(KV * Dh, "k_proj")(x).reshape(B, L, KV, Dh)
        v = dense(KV * Dh, "v_proj")(x).reshape(B, L, KV, Dh)
        scale = 1.0 / (Dh ** 0.5)

        if decode:
            return self._decode(
                q, k, v, cos, sin, scale, dense, positions, block_tables
            )

        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
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
        return dense(cfg.d_model, "o_proj")(o)

    def _decode(
        self, q, k, v, cos, sin, scale, dense, positions=None,
        block_tables=None,
    ):
        """KV-cache step: write this call's K/V at the running index into
        static (B, max_seq_len) buffers (flax "cache" collection), attend
        causally over the cache. One code path serves prefill (L = prompt
        length at index 0) and decode (L = 1) — static shapes throughout,
        so XLA compiles exactly two programs for the whole generate loop.
        cos/sin must cover max_seq_len; RoPE uses ABSOLUTE positions via a
        dynamic slice at the cache index.

        `positions` ((B,) int32, optional) switches to PER-SAMPLE cache
        indices: row b's K/V land at positions[b] and row b attends keys
        <= its own position — the serve engine's slot batch, where every
        row is an independent request at its own depth. The scalar cache
        index is neither read nor advanced on this path (per-slot lengths
        live with the caller).

        `block_tables` ((B, nb) int32, requires `positions`) switches the
        cache variables from per-row dense buffers to a PAGED block pool
        shared by every row: k/v are (num_blocks, block_size, KV, Dh) and
        row b's logical block j lives at physical block
        `block_tables[b, j]`. Writes scatter each token to
        (block, offset) through a flat view — positions whose logical
        block is unallocated (table entry == num_blocks) or out of range
        fall out of bounds and are DROPPED, which is what lets a parked
        (retired) slot lane and a padded prefill chunk ride through the
        step without touching any live request's blocks. Reads attend
        the row's logical layout under the same absolute-position causal
        mask, by one of the two paths `_decode_paged` describes; there is
        no "index" variable on this path (the pool has no per-row
        cursor)."""
        from jax import lax

        cfg = self.cfg
        if not cfg.causal:
            raise ValueError(
                "decode=True requires a causal model (the KV-cache step "
                "attends positions <= index); causal=False configs have "
                "no autoregressive decode"
            )
        B, L, KV, Dh = k.shape
        H = cfg.n_heads
        M = cfg.max_seq_len
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
            return self._decode_paged(
                q, k, v, cos, sin, scale, dense, positions, block_tables
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
        if positions is None:
            idx = ci.value
            pos_cos = lax.dynamic_slice_in_dim(cos, idx, L, axis=0)
            pos_sin = lax.dynamic_slice_in_dim(sin, idx, L, axis=0)
            q = apply_rope(q, pos_cos, pos_sin)
            k = apply_rope(k, pos_cos, pos_sin)
            with jax.named_scope("kv_scatter"):
                kf = lax.dynamic_update_slice_in_dim(ck.value, k, idx, axis=1)
                vf = lax.dynamic_update_slice_in_dim(cv.value, v, idx, axis=1)
            if is_initialized:
                ck.value = kf
                cv.value = vf
                ci.value = idx + L
            q_pos = idx + jnp.arange(L)
            mask = key_pos[None, :] <= q_pos[:, None]  # causal over cache
            mask = mask[None]  # (1, L, M) broadcast over batch
        else:
            idx = positions.astype(jnp.int32)  # (B,)
            pos = idx[:, None] + jnp.arange(L)[None, :]  # (B, L) absolute
            q = apply_rope_batched(q, cos[pos], sin[pos])
            k = apply_rope_batched(k, cos[pos], sin[pos])
            write = jax.vmap(
                lambda buf, upd, i: lax.dynamic_update_slice_in_dim(
                    buf, upd, i, axis=0
                )
            )
            with jax.named_scope("kv_scatter"):
                kf = write(ck.value, k, idx)
                vf = write(cv.value, v, idx)
            if is_initialized:
                ck.value = kf
                cv.value = vf
            mask = key_pos[None, None, :] <= pos[:, :, None]  # (B, L, M)
        # GQA: group the query heads and attend against the UN-repeated
        # cache — repeating the (B, M, KV, Dh) buffers up to H heads per
        # step would forfeit the KV-cache bandwidth saving GQA exists for
        rep = H // KV
        with jax.named_scope("cache_attention"):
            qg = q.reshape(B, L, KV, rep, Dh)
            s = jnp.einsum("blkrd,bmkd->bkrlm", qg, kf) * scale  # (B,KV,rep,L,M)
            s = jnp.where(mask[:, None, None], s.astype(jnp.float32), -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(vf.dtype)
            o = jnp.einsum("bkrlm,bmkd->blkrd", p, vf).reshape(B, L, H * Dh)
        return dense(cfg.d_model, "o_proj")(o)

    def _decode_paged(
        self, q, k, v, cos, sin, scale, dense, positions, block_tables
    ):
        """Paged-pool variant of the per-sample decode path (see _decode).

        The cache collection holds ONE (num_blocks, block_size, KV, Dh)
        K/V pool shared by all B rows; `block_tables` (B, nb) maps each
        row's logical blocks onto it. Token at absolute position p of
        row b writes to flat pool index
        `block_tables[b, p // bs] * bs + p % bs`; invalid logical blocks
        (table entry == num_blocks) and positions past the table push
        the flat index out of bounds, where `mode="drop"` discards the
        write.

        Attention then takes one of two paths, chosen from what this
        call can see (`ops.paged_decode_ok`: query length, pool shape
        and dtype, table shape — no option names it). A DECODE call
        (L == 1) on a pool that is not quantized, at a head size Mosaic
        tiles, runs `ops.paged_decode_attention`: one kernel that reads
        each row's pages out of the pool, as many as the row's length
        and leading valid table entries give it. Every other call (prefill chunks,
        int8 pools, the tiny head sizes of the CPU tests) gathers the
        row's logical K/V layout (`ops.gather_paged_kv`) and masks a
        dense einsum by absolute position. On both paths dropped or
        garbage regions are never attended (every key <= a live row's
        position sits in an allocated block — the engine allocates
        before it writes), and a parked row's output is finite and
        ignored by the scheduler.

        A QUANTIZED pool (int8 k/v plus `k_scale`/`v_scale` planes —
        `serve/cache.py::init_paged_cache(quantized=True)`) is detected
        from the cache collection: writes quantize each token's K/V
        vector per kv-head (`ops.quant.quantize_kv`) and scatter value
        and scale through the SAME flat index (same drop semantics);
        reads dequantize inside `ops.gather_paged_kv`, so the scores/
        softmax/output math below is identical in both modes."""
        from ..ops import (
            gather_paged_kv,
            paged_decode_attention,
            paged_decode_ok,
        )
        from ..ops.quant import quantize_kv

        cfg = self.cfg
        B, L, KV, Dh = k.shape
        H = cfg.n_heads
        M = cfg.max_seq_len
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
        q = apply_rope_batched(q, cos[safe], sin[safe])  # dropped below
        k = apply_rope_batched(k, cos[safe], sin[safe])

        lb = pos // bs  # (B, L) logical block
        off = pos % bs
        phys = jnp.take_along_axis(
            block_tables, jnp.clip(lb, 0, nb - 1), axis=1
        )  # (B, L) physical block id, == nblk when unallocated
        flat = jnp.where(lb < nb, phys * bs + off, nblk * bs)  # OOB sentinel
        flat = flat.reshape(B * L)

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
        if paged_decode_ok(L, ck.value, block_tables):
            with jax.named_scope("cache_attention"):
                o = paged_decode_attention(
                    q[:, 0], ck.value, cv.value, block_tables, idx, scale
                ).reshape(B, L, H * Dh)
            return dense(cfg.d_model, "o_proj")(o)
        with jax.named_scope("kv_gather"):
            kf, vf = gather_paged_kv(
                ck.value, cv.value, block_tables,
                k_scale=cks.value if quantized else None,
                v_scale=cvs.value if quantized else None,
                out_dtype=cfg.dtype,
            )
        with jax.named_scope("cache_attention"):
            Mb = nb * bs  # logical key span the tables cover (>= M)
            key_pos = jnp.arange(Mb)
            mask = key_pos[None, None, :] <= pos[:, :, None]  # (B, L, Mb)
            rep = H // KV
            qg = q.reshape(B, L, KV, rep, Dh)
            s = jnp.einsum("blkrd,bmkd->bkrlm", qg, kf) * scale
            s = jnp.where(mask[:, None, None], s.astype(jnp.float32), -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(vf.dtype)
            o = jnp.einsum("bkrlm,bmkd->blkrd", p, vf).reshape(B, L, H * Dh)
        return dense(cfg.d_model, "o_proj")(o)


def _flash_ok(L: int, Dh: int) -> bool:
    """Whether the flash kernel can take this shape: L divisible by the
    EFFECTIVE block sizes (`resolved_block_sizes` fits env/table
    candidates so they tile L whenever possible) and head_dim within the
    kernel's VMEM tile. A `use_flash=True` model that lands on dense
    attention instead says so once per shape (the O(L^2) logits are a
    different memory and speed regime, not a detail)."""
    from ..ops.flash_attention import resolved_block_sizes

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


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        F = cfg.ffn_dim
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=cfg.dtype, name=name
        )
        gate = dense(F, "gate_proj")(x)
        up = dense(F, "up_proj")(x)
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


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self, x, cos, sin, decode: bool = False, positions=None,
        block_tables=None,
    ):
        cfg = self.cfg
        x = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.norm_eps, name="attn_norm")(x), cos, sin, decode,
            positions, block_tables,
        )
        mlp_cls = MoE if cfg.n_experts > 0 else MLP
        x = x + mlp_cls(cfg, name="mlp")(RMSNorm(cfg.norm_eps, name="mlp_norm")(x))
        return x


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self, tokens, decode: bool = False, positions=None,
        block_tables=None,
    ):
        """tokens: (B, L) int32 → logits (B, L, vocab) fp32.

        `decode=True` switches attention to the KV-cache path (flax
        "cache" collection; apply with `mutable=["cache"]`): call once
        with the prompt (prefill), then with one token at a time —
        `models/generate.py` wraps the loop. `positions` ((B,) int32)
        selects PER-SAMPLE cache indices instead of the shared scalar
        index — the serve engine's slot-batch decode (`serve/`), where
        each row advances from its own depth. `block_tables` ((B, nb)
        int32, with `positions`) additionally switches the cache to the
        serve engine's PAGED block pool (`serve/cache.py`): one
        (num_blocks, block_size, kv_heads, head_dim) K/V pool per layer
        shared by all rows, indexed through per-row block tables."""
        cfg = self.cfg
        x = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="tok_embed"
        )(tokens)
        rope_len = cfg.max_seq_len if decode else tokens.shape[1]
        cos, sin = rope_freqs(cfg.head_dim, rope_len, cfg.rope_theta)
        # remat path: `decode` must NOT flow through nn.remat as a traced
        # positional (TracerBoolConversionError at `if decode:`); the
        # rematted path is always decode=False, so rely on the default
        use_remat = cfg.remat and not decode
        block_cls = nn.remat(Block) if use_remat else Block
        for i in range(cfg.n_layers):
            if use_remat:
                x = block_cls(cfg, name=f"layers_{i}")(x, cos, sin)
            else:
                x = block_cls(cfg, name=f"layers_{i}")(
                    x, cos, sin, decode, positions, block_tables
                )
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype, name="lm_head"
        )(x)
        return logits.astype(jnp.float32)


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
