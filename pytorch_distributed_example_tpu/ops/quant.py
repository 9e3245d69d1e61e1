"""Block-scaled quantization — the shared layer under the quantized
collectives (`parallel/comm_hooks.blockwise_quant_hook`) and the int8
paged KV cache (`serve/cache.PagedKVCache(quantized=True)`).

EQuARX (arxiv 2506.17615) shows block-quantized all-reduce inside XLA
reaches ~2x at negligible quality loss; the machinery is one codec used
two ways:

* **Gradient plane** — `quantized_all_reduce`: an all-reduce whose WIRE
  bytes are ~8-bit in BOTH phases. The lowering is
  quantize -> reduce-scatter in wire format (`lax.all_to_all` of the
  int8 payload + per-block f32 scales) -> local dequant-accumulate in
  f32 -> re-quantize the partial sums -> all-gather in wire format ->
  dequant. This is what the old `quantize_hook` did NOT do (it psum'd
  int32 — 4-byte wire, zero savings); tests pin the wire dtype by
  jaxpr inspection.
* **KV plane** — `quantize_kv`/`dequantize_kv`: per-(token, kv-head)
  max-abs scales over the head dim, the quantize-on-scatter /
  dequant-on-gather pair the paged attention path uses so the attention
  math itself stays f32/bf16.

Wire formats:

* ``"int8"`` — symmetric round-to-nearest onto [-127, 127] with one f32
  scale per `block_size` elements (scale overhead 4/block_size per
  element: ~1.6% at the default 256).
* ``"fp8"`` — values snapped to the float8_e4m3 grid but shipped in a
  BF16 CONTAINER (2 bytes/element on the wire): XLA collectives on f8
  dtypes are not portable across this repo's backends, so fp8 here
  buys the e4m3 value grid (for accuracy studies) at bf16 wire cost,
  not 1-byte wire. int8 is the bandwidth row.

Everything here is jnp-level (no Pallas): the codec fuses into the
surrounding program and the collectives lower to the same ICI ops the
unquantized path uses, just narrower.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

from ..numerics import numerics_contract

DEFAULT_BLOCK_SIZE = 256
_FP8_MAX = 448.0  # float8_e4m3fn largest finite
WIRE_FORMATS = ("int8", "fp8")


def _qmax(bits: int) -> int:
    return 2 ** (bits - 1) - 1


@numerics_contract(
    "tolerance",
    note="symmetric int8 round-trip: |dq - x| <= blockwise amax / qmax "
    "per element (data-dependent envelope; see tests/test_quant.py)",
)
def quantize_blockwise(
    x, block_size: int = DEFAULT_BLOCK_SIZE, bits: int = 8
):
    """Symmetric block-scaled int quantization along the LAST axis.

    x: (..., n) with n % block_size == 0. Returns
    (q int8 (..., n), scales f32 (..., n // block_size)) with
    q = round(x / scale) clipped to [-qmax, qmax] and
    scale = blockwise amax / qmax. Zero blocks get a tiny positive
    scale so dequant is exactly zero (no 0/0).
    """
    import jax.numpy as jnp

    if x.shape[-1] % block_size:
        raise ValueError(
            f"last dim {x.shape[-1]} not divisible by block_size "
            f"{block_size} (pad upstream)"
        )
    qmax = _qmax(bits)
    shape = x.shape
    xb = x.astype(jnp.float32).reshape(
        shape[:-1] + (shape[-1] // block_size, block_size)
    )
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scales = jnp.maximum(amax, 1e-30) / qmax
    q = jnp.clip(jnp.round(xb / scales[..., None]), -qmax, qmax)
    return q.astype(jnp.int8).reshape(shape), scales


def dequantize_blockwise(q, scales, block_size: int = DEFAULT_BLOCK_SIZE):
    """Inverse of `quantize_blockwise` (f32 output)."""
    import jax.numpy as jnp

    shape = q.shape
    qb = q.astype(jnp.float32).reshape(
        shape[:-1] + (shape[-1] // block_size, block_size)
    )
    return (qb * scales[..., None]).reshape(shape)


def quantize_blockwise_fp8(x, block_size: int = DEFAULT_BLOCK_SIZE):
    """Block-scaled fp8(e4m3)-on-bf16-container quantization.

    Values are scaled into the e4m3 range, snapped to the e4m3 grid by a
    float8 round trip, and returned in a BF16 container (the portable
    wire dtype — see module docstring). Scales are f32 per block.
    """
    import jax.numpy as jnp

    if x.shape[-1] % block_size:
        raise ValueError(
            f"last dim {x.shape[-1]} not divisible by block_size "
            f"{block_size} (pad upstream)"
        )
    shape = x.shape
    xb = x.astype(jnp.float32).reshape(
        shape[:-1] + (shape[-1] // block_size, block_size)
    )
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scales = jnp.maximum(amax, 1e-30) / _FP8_MAX
    snapped = (xb / scales[..., None]).astype(jnp.float8_e4m3fn)
    return snapped.astype(jnp.bfloat16).reshape(shape), scales


def dequantize_blockwise_fp8(q, scales, block_size: int = DEFAULT_BLOCK_SIZE):
    """Inverse of `quantize_blockwise_fp8` (f32 output) — same
    scale-multiply as the int8 dequant, just over a bf16 container."""
    return dequantize_blockwise(q, scales, block_size)


def _wire_encode(x, wire: str, block_size: int, bits: int = 8):
    if wire == "int8":
        return quantize_blockwise(x, block_size, bits=bits)
    if wire == "fp8":
        return quantize_blockwise_fp8(x, block_size)
    raise ValueError(f"unknown wire format {wire!r}; have {WIRE_FORMATS}")


def _wire_decode(q, scales, wire: str, block_size: int):
    if wire == "int8":
        return dequantize_blockwise(q, scales, block_size)
    if wire == "fp8":
        return dequantize_blockwise_fp8(q, scales, block_size)
    raise ValueError(f"unknown wire format {wire!r}; have {WIRE_FORMATS}")


def wire_itemsize(wire: str) -> int:
    """Bytes per element on the wire for a format (fp8 ships in a bf16
    container — see module docstring)."""
    return {"int8": 1, "fp8": 2}[wire]


def allreduce_wire_bytes(
    n: int, world: int, wire: Optional[str], block_size: int = DEFAULT_BLOCK_SIZE
) -> int:
    """Per-rank wire bytes one all-reduce of n elements moves under the
    ring model (2 (W-1)/W traffic), computed from shapes. `wire`
    None/'f32' = 4-byte, 'bf16' = 2-byte dense; quantized formats pay
    `wire_itemsize` per element plus 4 bytes per block of scale in both
    phases."""
    if world <= 1:
        return 0
    if wire in (None, "f32"):
        per_elem, scale = 4.0, 0.0
    elif wire == "bf16":
        per_elem, scale = 2.0, 0.0
    else:
        per_elem = float(wire_itemsize(wire))
        scale = 4.0 / block_size
    return int(2 * (world - 1) / world * n * (per_elem + scale))


@numerics_contract(
    "tolerance",
    rtol=5e-2,
    atol=5e-3,
    note="wire-quantized mean vs exact mean (PR 7, EQuARX-style "
    "envelope; tests/test_quant.py verifies at exactly this rtol/atol)",
)
def quantized_all_reduce(
    x,
    axis_name,
    *,
    wire: str = "int8",
    block_size: int = DEFAULT_BLOCK_SIZE,
    bits: int = 8,
    mean: bool = True,
    with_residual: bool = False,
):
    """Wire-quantized all-reduce over a mapped axis (shard_map/pmap body).

    Lowering (both phases ~wire-width on the ICI, unlike an int32 psum):

    1. flatten + pad the local buffer to `world * shard` elements,
       `shard` block-aligned; view as (world, shard) rows;
    2. block-quantize every row, `lax.all_to_all` the quantized payload
       and per-block scales — the reduce-scatter data phase, each rank
       ends up owning every rank's version of ITS shard;
    3. dequant-accumulate the world rows in f32 (the combine stays full
       precision, the ring-flash f32-combine discipline);
    4. re-quantize the local partial sum, `lax.all_gather` payload +
       scales — the broadcast phase, again wire-width;
    5. dequant, unpad, reshape.

    Returns the SUM (or mean) in x's dtype. `with_residual=True` also
    returns the LOCAL phase-1 compression residual
    ``x_f32 - dequant(quant(x))`` (f32, x's shape) — the error-feedback
    carry: phase-2's requantization error is not locally observable and
    stays uncompensated (second-order; it requantizes values already
    near the grid).

    `bits` (int8 wire only, 2..8) narrows the value grid inside the
    1-byte container — same wire bytes, lower fidelity; the bandwidth
    row is bits=8.

    TINY buffers fall back to an EXACT f32 psum: the row layout pads to
    `world * block_size` elements, so below ~`world * block_size / 4`
    the padded quantized path would move MORE bytes than a dense f32
    ring all-reduce (e.g. a 64-element bias at world 8, block 256:
    ~1.8 KB/rank/phase quantized vs ~450 B dense). Exact is both
    cheaper and lossless there; the residual is zero.
    """
    import jax.numpy as jnp
    from jax import lax

    if wire == "int8" and not 2 <= bits <= 8:
        raise ValueError(f"int8 wire carries 2..8 bit grids, got {bits}")
    W = lax.psum(1, axis_name)  # static axis size (python-int operand)
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.size
    if n * 4 < W * block_size:  # padding would exceed dense f32 wire
        out = lax.psum(flat, axis_name)
        if mean:
            out = out / W
        out = out.reshape(x.shape).astype(x.dtype)
        if with_residual:
            return out, jnp.zeros(x.shape, jnp.float32)
        return out
    shard = -(-n // (W * block_size)) * block_size
    pad = W * shard - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    rows = flat.reshape(W, shard)

    q, s = _wire_encode(rows, wire, block_size, bits)
    if with_residual:
        dq_local = _wire_decode(q, s, wire, block_size)
        residual = (
            (rows - dq_local).reshape(-1)[:n].reshape(x.shape)
        )
    if W > 1:
        qx = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
        sx = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0)
    else:
        qx, sx = q, s
    part = _wire_decode(qx, sx, wire, block_size).sum(axis=0)  # (shard,) f32

    q2, s2 = _wire_encode(part[None], wire, block_size, bits)
    if W > 1:
        qg = lax.all_gather(q2[0], axis_name)  # (W, shard) wire dtype
        sg = lax.all_gather(s2[0], axis_name)
    else:
        qg, sg = q2, s2
    out = _wire_decode(qg, sg, wire, block_size).reshape(-1)
    if pad:
        out = out[:n]
    if mean:
        out = out / W
    out = out.reshape(x.shape).astype(x.dtype)
    if with_residual:
        return out, residual
    return out


# ---------------------------------------------------------------------------
# KV-cache codec: per-(token, kv-head) scales over the head dim
# ---------------------------------------------------------------------------


@numerics_contract(
    "tolerance",
    note="per-(token, kv-head) int8 KV round-trip: |dq - x| <= vector "
    "amax / qmax (PR 11; token-match-rate claims live on the serve "
    "plane, see tests/test_serve_paged.py)",
)
def quantize_kv(x, bits: int = 8):
    """Quantize K/V vectors for the paged cache: x (..., Dh) ->
    (q int8 (..., Dh), scales f32 (...,)) with ONE max-abs scale per
    leading index — per (token-slot, kv-head) when called on the
    (B, L, KV, Dh) tensors the decode path writes. A per-vector scale is
    what makes QUANTIZE-ON-SCATTER possible: each token's write is
    self-contained, so landing it in a shared block never requires
    requantizing the block's earlier tokens."""
    import jax.numpy as jnp

    qmax = _qmax(bits)
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scales = jnp.maximum(amax, 1e-30) / qmax
    q = jnp.clip(jnp.round(x32 / scales[..., None]), -qmax, qmax)
    return q.astype(jnp.int8), scales


def dequantize_kv(q, scales, dtype):
    """Inverse of `quantize_kv`, cast to the attention math dtype."""
    import jax.numpy as jnp

    return (q.astype(jnp.float32) * scales[..., None]).astype(dtype)
