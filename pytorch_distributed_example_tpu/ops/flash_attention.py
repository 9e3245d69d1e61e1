"""Flash attention — Pallas TPU kernels (forward + backward), custom VJP.

The hot op of every transformer in this framework (SURVEY.md §2.3's
long-context obligation; used standalone, under Ulysses, and as the block
kernel behind sequence parallelism). Design per the TPU kernel playbook
(/opt/skills/guides/pallas_guide.md):

* forward (`flash_fwd`): one grid step per (batch·head, q-block); K/V
  stream through a `fori_loop` of `block_k` slices held in VMEM;
  online-softmax accumulator in fp32; logits never materialize in HBM
  (O(L) memory, not O(L²)).
* backward: flash-style recomputation — saves only (O, LSE) residuals;
  `delta = rowsum(dO·O)` is a cheap jnp preprocess. While q and dO fit
  VMEM whole, ONE kernel (`flash_bwd`: grid over k-blocks, loop over
  q-blocks) computes the scores, p and dlogits of a block pair once and
  does all three products from them, dQ accumulating in an fp32 VMEM
  scratch across the k-blocks: five MXU products a pair. Past that
  (`_use_streaming`) two kernels (`flash_bwd_dkdv`, `flash_bwd_dq`) ride
  the counterpart blocks on the grid and each recompute p: seven.
* the MXU takes its operands in the dtype they have in memory (a product
  of two bf16 values is exact in fp32, and Mosaic rounds an fp32 operand
  to bf16 at default precision anyway: measured bit for bit on v5e,
  PERF.md §6, PR 34); softmax statistics and accumulators are fp32.
* causal masking by global positions; blocks strictly above the diagonal
  are skipped by bounding the loop (upper-triangular work never
  executes), and of the visited pairs only those the diagonal crosses
  build and apply the mask.

On non-TPU backends (the 8-device CPU test mesh) the kernels run in
interpreter mode automatically — same code path, bitwise-comparable math.

Layout note: public API takes (B, L, H, D) to match
`parallel/context_parallel.py`; kernels internally use (B·H, L, D).
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from .._compat import shard_map_fn
from ..utils.remat import FLASH_LSE, FLASH_OUT

NEG_INF = -1e30


def _compiler_params(pltpu):
    """The streamed kernels' (parallel, parallel, arbitrary) grid
    semantics."""
    return pltpu.CompilerParams(
        dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                             pltpu.ARBITRARY),
    )


def _vmem_limit(buffered: int, scratch: int, block_q: int, block_k: int) -> int:
    """Scoped VMEM a resident kernel asks for: its operands' blocks as
    Pallas double-buffers them, its scratch, and room for eight live
    (block_q, block_k) fp32 tiles; never under the 16 MB a v5e kernel gets
    unasked, which hold both kernels at the train cells' shape (L 4096,
    Dh 128, bf16, blocks of 512) but not fp32 inputs, L 8192 or blocks of
    1024."""
    return max(16 << 20, 2 * buffered + scratch + 8 * block_q * block_k * 4)


def _interpret_default() -> bool:
    """Compile where Mosaic can lower (a TPU backend); interpret elsewhere.

    On a TPU backend the answer is always "compile": no setting reaches
    the interpreter there. `TDX_FLASH_INTERPRET=0` exists for one case
    only — AOT-compiling for a DEVICELESS TPU topology from a CPU-pinned
    process (`tests/test_aot_topology.py`), where the backend is not
    "tpu" but the target is.
    """
    import os

    if jax.default_backend() == "tpu":
        return False
    return os.environ.get("TDX_FLASH_INTERPRET") != "0"


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


# a @ b, a @ b.T and a.T @ b as `lax.dot_general` dimension numbers
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    """One MXU product into fp32. bf16 operands have no precision to ask
    for (their products are exact in fp32), and Mosaic refuses the request
    that an ambient `jax_default_matmul_precision="highest"` would make
    of them; fp32 operands keep following it."""
    precision = lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=jnp.float32
    )


def _crossing_pairs(causal, rows, cols):
    """How many of the block pairs a `rows`-long block visits along the
    other axis (blocks of `cols`) the causal diagonal crosses: a static
    count when one size divides the other (every row of the tuned table
    and every caller in the tree), and those pairs alone take the mask.
    None: every visited pair takes it."""
    if not causal:
        return 0
    if rows % cols and cols % rows:
        return None
    return max(1, rows // cols)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, block_q, block_k, seq_len):
    D = q_ref.shape[-1]
    i = pl.program_id(1)
    q_start = i * block_q
    # scaled in fp32, handed to the MXU in the input's dtype
    q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)  # (block_q, D)

    def pair(j, carry, masked):
        m, l, acc = carry
        cols = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        s = _dot(q, k, _NT)  # (bq, bk)
        if masked:
            q_pos = q_start + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # a row with nothing visible yet (only under the mask) keeps exp finite
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new) if masked else m_new
        p = jnp.exp(s - m_safe[:, None])
        alpha = jnp.exp(m - m_new)  # finite: both -1e30 → exp(0)=1, acc is 0
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + _dot(p.astype(v.dtype), v)
        return m_new, l, acc

    carry = (
        jnp.full((block_q,), NEG_INF, jnp.float32),
        jnp.zeros((block_q,), jnp.float32),
        jnp.zeros((block_q, D), jnp.float32),
    )
    under = functools.partial(pair, masked=False)
    crossing = functools.partial(pair, masked=True)
    # k-blocks that intersect the triangle for this q block
    num_k = (q_start + block_q - 1) // block_k + 1 if causal else seq_len // block_k
    n_cross = _crossing_pairs(causal, block_q, block_k)
    if n_cross is None:
        carry = lax.fori_loop(0, num_k, crossing, carry)
    else:
        carry = lax.fori_loop(0, num_k - n_cross, under, carry)
        for t in range(n_cross):  # unrolled: a second loop costs more than the mask
            carry = crossing(num_k - n_cross + t, carry)
    m, l, acc = carry

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse carried as (..., 1): TPU block tiling wants the lane dim equal to
    # the (size-1) array dim, with block_q on the sublane axis
    lse_ref[0] = (m + jnp.log(l_safe))[:, None]


def _fwd(q, k, v, scale, causal, block_q, block_k, interpret,
         out_dtype=None):
    """q,k,v: (BH, L, D) → (o, lse). `out_dtype` overrides the output
    dtype (default q.dtype): the ring-attention combine requests f32 so
    per-shard partials come straight from the kernel's f32 accumulator
    instead of a bf16-rounded output (ADVICE r5 #2)."""
    from jax.experimental.pallas import tpu as pltpu

    BH, L, D = q.shape
    itemsize = q.dtype.itemsize
    if _use_streaming(L, D, itemsize):
        return _fwd_streamed(q, k, v, scale, causal, block_q, block_k,
                             interpret, out_dtype)
    grid = (BH, L // block_q)

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        seq_len=L,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, L, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, L, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((BH, L, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL),
            # k and v whole; q, o and lse (a value a 128-lane row) by block
            vmem_limit_bytes=_vmem_limit(
                2 * L * D * itemsize + block_q * (D * 2 * itemsize + 512), 0,
                block_q, block_k,
            ),
        ),
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# streamed variants: k/v blocks ride the GRID instead of sitting whole in
# VMEM. The resident kernels hold the full counterpart operand in VMEM
# (k/v for the forward above, q/do and dQ's accumulator for the one-pass
# backward below), which is fastest while it fits but exceeds the ~16 MB
# scoped-VMEM limit near L·D ≈ 1.5M elements (measured on the forward:
# L=16384, D=128 OOMs at 16.75M needed). Past `_stream_threshold` the
# pallas grid gains a third dimension over counterpart blocks; the online
# accumulators live in VMEM scratch that persists across the innermost
# (ARBITRARY) grid dimension, and outputs are written at its last step —
# the standard TPU flash streaming scheme. O(block) VMEM at any L.
# ---------------------------------------------------------------------------


def _stream_threshold_elems(itemsize: int) -> int:
    """Counterpart-residency limit in ELEMENTS of one (L, D) operand.
    Default 6 MB across the two resident operands (k+v, double-buffered
    pairs then stay under the 16 MB scoped limit); dtype-aware — fp32
    halves the element budget. TDX_FLASH_STREAM=1/0 forces on/off."""
    import os

    mb = float(os.environ.get("TDX_FLASH_VMEM_MB", "6"))
    return int(mb * (1 << 20) / 2 / itemsize)


def _use_streaming(L: int, D: int, itemsize: int = 2) -> bool:
    import os

    env = os.environ.get("TDX_FLASH_STREAM")
    # strict parse (ADVICE r5 #3): '1'/'0' force on/off, unset or ''
    # means auto; anything else raises — a typo like 'true' silently
    # forcing OFF would re-enable VMEM-resident kernels at lengths
    # that OOM (L=16k, D=128)
    if env in (None, ""):
        return L * D > _stream_threshold_elems(itemsize)
    if env == "1":
        return True
    if env == "0":
        return False
    raise ValueError(
        f"TDX_FLASH_STREAM={env!r} is invalid: use '1' (force streamed), "
        "'0' (force resident), or unset/'' (auto by operand size)"
    )


def _fwd_kernel_streamed(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s,
    *, scale, causal, block_q, block_k,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = i * block_q
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m = m_s[:, 0]
        l = l_s[:, 0]
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_s[...] = acc_s[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_s[:, 0] = m_new
        l_s[:, 0] = l_new

    if causal:
        # blocks strictly above the diagonal contribute nothing; their
        # grid steps skip the compute (the block DMA still happens)
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_s[:, 0], 1e-30)
        o_ref[0] = (acc_s[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_s[:, 0] + jnp.log(l_safe))[:, None]


def _fwd_streamed(q, k, v, scale, causal, block_q, block_k, interpret,
                  out_dtype=None):
    from jax.experimental.pallas import tpu as pltpu

    BH, L, D = q.shape
    grid = (BH, L // block_q, L // block_k)
    kernel = functools.partial(
        _fwd_kernel_streamed,
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((BH, L, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=_compiler_params(pltpu),
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_acc, *, scale, causal, block_q, block_k, seq_len
):
    """dQ, dK and dV in one pass over the block pairs of key block `j`.

    Scores are held TRANSPOSED, (block_k, block_q): lse and delta are then
    rows, (1, block_q), that broadcast down the sublanes (as columns they
    tile to 128 lanes a value: 2 MB each at L 4096), dV and dK are plain
    products and only dQ's takes a transposed operand. dQ accumulates in
    `dq_acc`, (L, D) fp32, across the key blocks (the grid's ARBITRARY
    axis) in the order the two-kernel form adds them, so the three
    gradients are that form's, bit for bit (v5e, PERF.md §6, PR 34)."""
    D = q_ref.shape[-1]
    j = pl.program_id(1)
    k_start = j * block_k
    k = k_ref[0]  # (block_k, D)
    v = v_ref[0]

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def pair(i, carry, masked):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        lse = lse_ref[0, :, rows]  # (1, block_q)
        delta = delta_ref[0, :, rows]
        st = _dot(k, q, _NT) * scale
        if masked:
            k_pos = k_start + lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
            q_pos = i * block_q + lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
            st = jnp.where(q_pos >= k_pos, st, NEG_INF)
        pt = jnp.exp(st - lse)  # (bk, bq); masked → exp(NEG_INF-lse)=0
        dpt = _dot(v, do, _NT)
        dst = (pt * (dpt - delta)).astype(q.dtype)  # dlogits, transposed
        dv = dv + _dot(pt.astype(do.dtype), do)
        dk = dk + _dot(dst, q) * scale
        dq_acc[rows, :] += _dot(dst, k, _TN) * scale
        return dk, dv

    carry = (jnp.zeros((block_k, D), jnp.float32), jnp.zeros((block_k, D), jnp.float32))
    under = functools.partial(pair, masked=False)
    crossing = functools.partial(pair, masked=True)
    num_q = seq_len // block_q
    # first q-block intersecting the triangle
    first_q = k_start // block_q if causal else 0
    n_cross = _crossing_pairs(causal, block_k, block_q)
    if n_cross is None:
        carry = lax.fori_loop(first_q, num_q, crossing, carry)
    else:
        for t in range(n_cross):
            carry = crossing(first_q + t, carry)
        carry = lax.fori_loop(first_q + n_cross, num_q, under, carry)
    dk, dv = carry
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkdv_kernel_streamed(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_s, dv_s, *, scale, causal, block_q, block_k,
):
    j = pl.program_id(1)   # k block (output)
    i = pl.program_id(2)   # q block (streamed)
    nq = pl.num_programs(2)
    k_start = j * block_k
    q_start = i * block_q

    @pl.when(i == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv_s[...] = dv_s[...] + jnp.dot(
            p.T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        dlogits = p * (dp - delta[:, None])
        dk_s[...] = dk_s[...] + jnp.dot(
            dlogits.T, q, preferred_element_type=jnp.float32
        ) * scale

    if causal:
        # q blocks entirely above the diagonal see only masked logits
        pl.when(q_start + block_q - 1 >= k_start)(_compute)
    else:
        _compute()

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _bwd_dq_kernel_streamed(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_s,
    *, scale, causal, block_q, block_k,
):
    i = pl.program_id(1)   # q block (output)
    j = pl.program_id(2)   # k block (streamed)
    nk = pl.num_programs(2)
    q_start = i * block_q
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        dlogits = p * (dp - delta[:, None])
        dq_s[...] = dq_s[...] + jnp.dot(
            dlogits, k, preferred_element_type=jnp.float32
        ) * scale

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _bwd_calls(q, k, v, do, lse, delta, scale, causal, block_q, block_k,
               interpret):
    """(dQ, dK, dV) for one (q-set, kv-set) pair given PRECOMPUTED
    lse/delta, both (BH, L, 1) fp32.

    The form follows the operand size as `_fwd`'s does: one kernel while
    q and dO (and dQ's fp32 accumulator) sit whole in VMEM, the two
    streamed kernels past that. Delta-taking so that the ring backward
    reuses it per kv shard with the ring's FINAL lse/delta."""
    from jax.experimental.pallas import tpu as pltpu

    BH, L, D = q.shape
    itemsize = q.dtype.itemsize
    if not _use_streaming(L, D, itemsize):
        whole = pl.BlockSpec((1, L, D), lambda b, j: (b, 0, 0))
        block = pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0))
        row = pl.BlockSpec((1, 1, L), lambda b, j: (b, 0, 0))
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel,
                scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                seq_len=L,
            ),
            grid=(BH, L // block_k),
            in_specs=[whole, block, block, whole, row, row],
            out_specs=[whole, block, block],
            out_shape=[jax.ShapeDtypeStruct((BH, L, D), q.dtype)] * 3,
            scratch_shapes=[pltpu.VMEM((L, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY),
                # q, dO and dQ whole, four (block_k, D) blocks, lse and
                # delta as 8-sublane rows; dQ's accumulator
                vmem_limit_bytes=_vmem_limit(
                    (3 * L + 4 * block_k) * D * itemsize + 2 * 8 * L * 4,
                    L * D * 4, block_q, block_k,
                ),
            ),
            name="flash_bwd",
            interpret=interpret,
        )(q, k, v, do, lse.reshape(BH, 1, L), delta.reshape(BH, 1, L))

    sem = _compiler_params(pltpu)
    q_blk = lambda b, j, i: (b, i, 0)  # dK/dV: grid (BH, k blocks, q blocks)
    k_blk = lambda b, j, i: (b, j, 0)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel_streamed,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        ),
        grid=(BH, L // block_k, L // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_blk),
            pl.BlockSpec((1, block_k, D), k_blk),
            pl.BlockSpec((1, block_k, D), k_blk),
            pl.BlockSpec((1, block_q, D), q_blk),
            pl.BlockSpec((1, block_q, 1), q_blk),
            pl.BlockSpec((1, block_q, 1), q_blk),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), k_blk),
            pl.BlockSpec((1, block_k, D), k_blk),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, L, D), q.dtype)] * 2,
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=sem,
        name="flash_bwd_dkdv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    q_blk = lambda b, i, j: (b, i, 0)  # dQ: grid (BH, q blocks, k blocks)
    k_blk = lambda b, i, j: (b, j, 0)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel_streamed,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        ),
        grid=(BH, L // block_q, L // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_blk),
            pl.BlockSpec((1, block_k, D), k_blk),
            pl.BlockSpec((1, block_k, D), k_blk),
            pl.BlockSpec((1, block_q, D), q_blk),
            pl.BlockSpec((1, block_q, 1), q_blk),
            pl.BlockSpec((1, block_q, 1), q_blk),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_blk),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=sem,
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k, interpret,
         dlse=None):
    # (BH, L, 1) — same tiling story as lse
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    if dlse is not None:
        # lse cotangent folds into delta: d_logits = p*(dp - delta)
        # generalizes to p*(dp - delta + dlse_row), since
        # d(lse)/d(logits) = softmax(logits) = p
        delta = delta - dlse.astype(jnp.float32)
    return _bwd_calls(q, k, v, do, lse, delta, scale, causal, block_q,
                      block_k, interpret)


# ---------------------------------------------------------------------------
# public API (custom VJP over (B, L, H, D))
# ---------------------------------------------------------------------------


def _to_bh(x):
    # (B, L, H, D) -> (B*H, L, D)
    B, L, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, L, D)


def _from_bh(x, B, H):
    BH, L, D = x.shape
    return x.reshape(B, H, L, D).transpose(0, 2, 1, 3)


def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    # o-only view of flash_with_lse — ONE custom_vjp definition to
    # maintain; the unused lse output's cotangent arrives as zeros and
    # costs a negligible (BH, L, 1) subtract in the backward
    return flash_with_lse(q, k, v, scale, causal, block_q, block_k,
                          interpret)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_with_lse(q, k, v, scale, causal, block_q, block_k, interpret):
    """(o, lse) with FULL differentiation through both outputs.

    For compositions that consume the log-sum-exp — ring attention's
    per-shard partial combine being the motivating one — the lse
    cotangent must reach the kernels: since d(lse)/d(logits) =
    softmax(logits) = p, it folds into the existing backward as
    `delta -> delta - dlse` (dlogits = p*(dp - delta + dlse_row)), so
    the same bwd kernels serve both VJPs. Shapes as `_fwd`:
    (BH, L, D) in, ((BH, L, D), (BH, L, 1)) out.

    The backward's residuals `o` and `lse` carry names (`utils.remat`):
    under a `jax.checkpoint` whose policy saves them the kernel never
    runs a second time; anywhere else a name is nothing.
    """
    return _fwd(q, k, v, scale, causal, block_q, block_k, interpret)


def _fwl_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    o, lse = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    # named HERE, inside the forward rule, and both: a name on the call's
    # output alone would save `o` and still re-run the kernel for `lse`.
    # lse is kept as (BH, L): the kernel writes (BH, L, 1), one float32 a
    # 128-lane row on the chip, 128 times the bytes
    o = checkpoint_name(o, FLASH_OUT)
    return (o, lse), (q, k, v, o, checkpoint_name(lse.squeeze(-1), FLASH_LSE))


def _fwl_bwd(scale, causal, block_q, block_k, interpret, res, cts):
    q, k, v, o, lse = res
    lse = lse[..., None]
    do, dlse = cts
    dq, dk, dv = _bwd(
        q, k, v, o, lse, do, scale, causal, block_q, block_k, interpret,
        dlse=dlse,
    )
    return dq, dk, dv


flash_with_lse.defvjp(_fwl_fwd, _fwl_bwd)


@functools.lru_cache(maxsize=1)
def _tuned_table() -> dict:
    """Checked-in block-size table (`flash_tuned.json`): per-geometry
    winners of chip sweeps. Keys are "L{seq}" plus "default"; the L4096
    row, the one length a benchmark cell runs, is PR 34's sweep of these
    kernels on a v5e (kernel ms a call in the row), the others come from
    an earlier machine and kernels, by a generator no longer in the tree.
    The file is tracked, so one that is missing or does not parse is a
    broken checkout and raises."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "flash_tuned.json")
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object, got {type(doc)}")
    return doc


_env_fit_warned: set = set()  # (env_name, requested, L, fitted) already warned


def resolved_block_sizes(
    L: int,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> tuple:
    """The effective (block_q, block_k) `flash_attention` will use for a
    given sequence length: per-call override (clamped to L only — an
    explicit block that cannot tile L still raises so misconfiguration
    is loud), else `TDX_FLASH_BLOCK_Q`/`TDX_FLASH_BLOCK_K` env, else
    the hardware-tuned table (`flash_tuned.json`: exact-L entry, then
    "default_long" for lengths in the streamed regime it was swept in,
    then "default"), else 128. Env/table candidates are FITTED: clamped
    to L and halved (128 fallback) until they tile L, so a default
    promoted from a long sweep cannot break shorter lengths. Callers
    that gate on divisibility (e.g. models.transformer._flash_ok) must
    check against THESE, not the hard-coded default."""
    import os

    tuned = _tuned_table()
    long_row = tuned.get("default_long") or {}
    row = tuned.get(f"L{L}")
    if row is None and long_row and L >= int(long_row.get("applies_from",
                                                          1 << 62)):
        row = long_row
    if row is None:
        row = tuned.get("default") or {}

    def fit(b):
        # clamp to L, then halve until it tiles; a non-power-of-two
        # candidate can halve PAST a valid divisor (768 -> 96 misses
        # 128 at L=1024), so fall back to 128 explicitly
        b = min(b, L)
        while b > 128 and L % b:
            b //= 2
        if L % b:
            b = min(128, L)
        return b

    def fit_env(b, env_name, from_env):
        fitted = fit(b)
        # warn (once per distinct alteration) when fit() changes an
        # ENV-provided block: per-call overrides raise loudly on a
        # non-tiling block, but a fleet-wide env misconfiguration would
        # otherwise run with a silently different size (ADVICE r5 #5)
        if from_env and fitted != b:
            key = (env_name, b, L, fitted)
            if key not in _env_fit_warned:
                _env_fit_warned.add(key)
                import warnings

                warnings.warn(
                    f"{env_name}={b} cannot tile L={L}; using {fitted} "
                    "instead — audit the fleet-wide env setting",
                    RuntimeWarning,
                    stacklevel=3,
                )
        return fitted

    if block_q is None:
        env_q = int(os.environ.get("TDX_FLASH_BLOCK_Q", 0))
        block_q = env_q or int(row.get("block_q", 0)) or 128
        block_q = fit_env(block_q, "TDX_FLASH_BLOCK_Q", bool(env_q))
    else:
        block_q = min(block_q, L)
    if block_k is None:
        env_k = int(os.environ.get("TDX_FLASH_BLOCK_K", 0))
        block_k = env_k or int(row.get("block_k", 0)) or 128
        block_k = fit_env(block_k, "TDX_FLASH_BLOCK_K", bool(env_k))
    else:
        block_k = min(block_k, L)
    return block_q, block_k


class _MeshPartition(threading.local):
    """(mesh, batch_axes, head_axes) while a `partitioned_over` context is
    open on this thread, else None."""

    spec = None


_partition = _MeshPartition()


@contextlib.contextmanager
def partitioned_over(mesh, batch_axes: Sequence[str], head_axes: Sequence[str] = ()):
    """Trace-time context: run `flash_attention` per device under a mesh.

    A Mosaic kernel is a custom call GSPMD cannot partition, so inside a
    jit whose operands are sharded over ``mesh`` (the FSDP/ZeRO/TP
    trainers) the kernel must be told its layout. While this context is
    open, `flash_attention` wraps itself in a `shard_map` over ``mesh``
    with q/k/v/o laid out `P(batch_axes, None, head_axes, None)`: each
    device runs the kernel on its local (batch-shard, head-shard) slice.
    Attention is independent per (batch row, head), so no collective is
    needed and none is introduced; L and D stay whole.

    The GSPMD trainer factories (`parallel/fsdp.py`) open it around their
    traced step; a hand-written `jax.jit` over `parallelize_module`'d
    params opens it the same way. Do NOT open it inside a `shard_map`
    region that already owns the mesh axes (the DDP step, ring
    attention): there the kernel is already local.
    """
    jmesh = getattr(mesh, "jax_mesh", mesh)
    sizes = dict(jmesh.shape)
    batch_axes, head_axes = tuple(batch_axes), tuple(head_axes)
    for ax in batch_axes + head_axes:
        if ax not in sizes:
            raise ValueError(
                f"partitioned_over: mesh has no axis {ax!r}: {tuple(sizes)}"
            )
    prev = _partition.spec
    _partition.spec = (jmesh, batch_axes, head_axes)
    try:
        yield
    finally:
        _partition.spec = prev


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Flash attention over (B, L, H, D) tensors; differentiable.

    Block sizes default to 128 (one MXU tile) and can be overridden per
    call or fleet-wide via `TDX_FLASH_BLOCK_Q` / `TDX_FLASH_BLOCK_K`.

    Constraints: L divisible by block sizes (pad upstream). Sequence
    length is otherwise unbounded: past ~L·D·itemsize ≈ 3 MB per
    operand the kernels switch automatically to the STREAMED variants
    (k/v blocks ride the pallas grid, O(block) VMEM — measured on
    hardware at L=64k single-chip, `flash_sweep_L65536_*`). Below that
    the VMEM-resident kernels are used (fastest while they fit);
    TDX_FLASH_STREAM=1/0 forces either. Ring attention over the mesh
    (parallel/context_parallel.py) remains the MULTI-chip long-context
    path and calls this kernel per shard.

    Under a GSPMD jit over several devices, open `partitioned_over`
    around the trace (the trainer factories do): the call then runs per
    device on its (batch, head) shard. Without it Mosaic refuses at
    lowering ("cannot be automatically partitioned").
    """
    B, L, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    bq, bk = resolved_block_sizes(L, block_q, block_k)
    if L % bq or L % bk:
        raise ValueError(f"seq len {L} must be divisible by block sizes ({bq},{bk})")
    if interpret is None:
        interpret = _interpret_default()

    def local(q, k, v):
        b, _, h, _ = q.shape
        o = _flash(_to_bh(q), _to_bh(k), _to_bh(v), scale, causal, bq, bk,
                   interpret)
        return _from_bh(o, b, h)

    if _partition.spec is None:
        return local(q, k, v)
    jmesh, batch_axes, head_axes = _partition.spec
    nb = math.prod(jmesh.shape[ax] for ax in batch_axes)
    nh = math.prod(jmesh.shape[ax] for ax in head_axes)
    if B % nb or H % nh:
        raise ValueError(
            f"flash_attention under mesh {dict(jmesh.shape)}: batch {B} must "
            f"divide over {batch_axes} (={nb}) and heads {H} over "
            f"{head_axes} (={nh}); the kernel runs on whole (row, head) "
            "slices and is never partitioned along L or D"
        )
    spec = jax.sharding.PartitionSpec(
        batch_axes or None, None, head_axes or None, None
    )
    return shard_map_fn(local, jmesh, (spec, spec, spec), spec)(q, k, v)


def paged_window_span(positions, L: int, window: int, bs: int, nb: int):
    """Which blocks of its table a window layer's call has to gather:
    (first_block (B,) int32, n_blocks). Row b's L queries sit at
    `positions[b] .. positions[b] + L - 1` and attend keys from
    `positions[b] - window + 1` on, so `n_blocks` blocks from the one
    that holds that key cover them all: `window + L - 1` keys and the
    partial blocks at both ends. `first_block` is cut so that the span
    stays inside the table's `nb` blocks; key j of the gathered layout
    is absolute position `first_block * bs + j`."""
    n_blocks = min(nb, -(-(window + L - 1) // bs) + 1)
    first_key = jnp.maximum(positions.astype(jnp.int32) - (window - 1), 0)
    return jnp.minimum(first_key // bs, nb - n_blocks), n_blocks


def gather_paged_kv(
    pool_k, pool_v, block_tables, k_scale=None, v_scale=None,
    out_dtype=None, first_block=None, n_blocks=None,
):
    """Materialize each row's LOGICAL K/V layout from a paged block pool.

    pool_k/pool_v: (num_blocks, block_size, KV, Dh) — the serve engine's
    shared block pool (`serve/cache.py`); block_tables: (B, nb) int32
    mapping row b's logical block j to a physical block id (entries ==
    num_blocks mark unallocated logical blocks; the gather clamps them
    to a real block and the caller's causal/length mask hides the
    garbage, exactly like padded prefill positions). Returns
    ((B, nb*block_size, KV, Dh), (B, nb*block_size, KV, Dh)) in logical
    position order, so downstream attention indexes keys by absolute
    position. It lowers to an XLA gather feeding the cache-attention
    einsum, and it moves the whole `nb * block_size` span of every row
    whatever the row's length: the path of int8 pools, head sizes Mosaic
    cannot tile and a window layer's chunk. A decode step and a prefill
    chunk on a plain pool read their pages in the kernels of
    `ops/paged_attention.py` instead and never call this
    (`ops.paged_kernel` decides). The KV-head
    axis passes through untouched, so a TP-sharded pool stays sharded
    through the gather.

    `k_scale`/`v_scale` ((num_blocks, block_size, KV) f32 — the int8
    pool's per-(token, kv-head) scale planes) switch on DEQUANT-IN-
    GATHER: scales ride the same table gather and multiply the int8
    payload back to `out_dtype` (the attention math dtype), so nothing
    downstream ever sees quantized values. The scale gather shards the
    same way on the KV-head axis under TP.

    `first_block` ((B,) int32) with `n_blocks` (static) gathers only
    that span of each row's table — a window layer's chunk moves the
    `window + chunk` keys it can attend (`paged_window_span`), not the
    table's span. Entries of the span that are invalid (freed behind the
    window, or not yet allocated) clamp like any other.
    """
    nblk, bs, KV, Dh = pool_k.shape
    if first_block is not None:
        block_tables = jax.vmap(
            lambda row, start: jax.lax.dynamic_slice(row, (start,), (n_blocks,))
        )(block_tables, first_block)
    B, nb = block_tables.shape

    def one(pool, scale):
        g = pool[block_tables]  # (B, nb, bs, KV, Dh), OOB ids clamp
        if scale is not None:
            s = scale[block_tables]  # (B, nb, bs, KV)
            g = (g.astype(jnp.float32) * s[..., None]).astype(
                out_dtype or jnp.float32
            )
        return g.reshape(B, nb * bs, KV, Dh)

    return one(pool_k, k_scale), one(pool_v, v_scale)
