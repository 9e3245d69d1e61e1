"""One token of the gated delta rule for every live row of a decode step,
over the serve engine's pool of recurrent state blocks, as ONE Pallas TPU
kernel: each live row's state is read once, updated, and written once back
into the pool it came from (`paged_delta_step`). `delta_kernel_ok` is the one
predicate: `models/transformer.py::LinearAttention` asks it and traces either
this kernel or the same update in `jax.numpy` (gather, two passes, scatter:
what the tests compare the kernel with).

Shape of the kernel (design per /opt/skills/guides/pallas_guide.md):

* The pool (blocks, H, dk, dv) float32 is an input AND the aliased output:
  nothing is copied, a block no live row holds is neither read nor written.
* A grid step is one (live row, group of `heads` heads): its state block
  rides in and out through the pipeline's own double buffers, indexed by
  the row's table entry (scalar prefetch). Rows are visited live rows
  first (`order`); the steps past the last live row keep the last live
  step's block indices, so the pipeline moves nothing for them, and their
  body does nothing.
* Inside, per head: S^T k and S^T q from the one block in VMEM, the rule's
  correction d = beta (v - alpha S^T k), the new state alpha S + k d^T and
  the output alpha S^T q + (k . q) d. k and q are needed down the sublanes
  (one value a state ROW), v, d and the output along the lanes: k and q
  ride in transposed, (dk, 128) a step with a head a lane, and alpha and
  beta repeated along the lanes beside v, all arranged outside (a few KB
  a row).
* The decay is one alpha a head (alpha: (B, H)), as above, or a VECTOR a
  head, one alpha a key channel (alpha: (B, H, dk)): it then varies down
  the sublanes, one value a state row, and rides in as a third group of
  lanes beside k and q; the row c of the state is decayed by alpha_c, and
  S^T (alpha k), S^T (alpha q) take the place of alpha S^T k, alpha S^T q.
  Which of the two a call is, is its `alpha`'s rank: two programs, and a
  model of one form traces the other nowhere.
* A row whose first token stands at position 0 (`fresh`) reads zeros
  whatever its block held.
* The state's rows of dv = 192 values are padded to 256 lanes by the
  device's tiling, in HBM and in VMEM: a block moves 4/3 of its bytes.
  A lane-dense storage of the state is what is left to win.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_default, _partition

LANES = 128
# heads a grid step takes: four blocks of them (two in, two out) stand in VMEM
HEADS_A_STEP = 10


def _heads_a_step(H: int) -> int:
    return max(h for h in range(1, min(H, HEADS_A_STEP) + 1) if H % h == 0)


def delta_kernel_ok(pool) -> bool:
    """Whether `paged_delta_step` takes a decode call over `pool`, the
    state pool (blocks, H, dk, dv) of a linear layer (an array or a
    `ShapeDtypeStruct`: shape and dtype alone are read): a float32 pool whose state rows fill whole sublane tiles (dk a multiple
    of 8) and at least half a lane tile (dv a multiple of 64), a step's
    heads' k and q (and a vector decay's alpha) in one lane tile, and no
    partition context (a tp engine is
    refused with linear layers). The toy widths of the CPU tests take the
    `jax.numpy` path, as small heads do in `ops.paged_kernel`."""
    _, H, dk, dv = pool.shape
    return (
        pool.dtype == jnp.float32 and dk % 8 == 0 and dv % 64 == 0
        and 3 * _heads_a_step(H) <= LANES and _partition.spec is None
    )


def _kernel(order_ref, table_ref, fresh_ref, live_ref, kq_ref, vab_ref,
            pool_ref, o_ref, out_ref, *, heads: int, channel: bool):
    i = pl.program_id(0)
    n_live = live_ref[0]

    @pl.when(jnp.logical_and(n_live == 0, jnp.logical_and(i == 0, pl.program_id(1) == 0)))
    def _():  # no live row at all: the one block the steps map to goes back as it came
        out_ref[...] = pool_ref[...]

    @pl.when(i < n_live)
    def _():
        fresh = fresh_ref[order_ref[i]] != 0
        # (dk, 128): lane h is head h's k, lane heads + h its q and, with a
        # decay a channel, lane 2 heads + h its alpha
        kq = kq_ref[0, 0]
        for h in range(heads):
            S = jnp.where(fresh, 0.0, pool_ref[0, h])  # (dk, dv)
            k, q = kq[:, h:h + 1], kq[:, heads + h:heads + h + 1]  # (dk, 1)
            # v, beta (and one alpha a head) as (1, dv) rows (repeated along
            # the lanes outside: Mosaic broadcasts along lanes or sublanes, not both)
            row = lambda j: vab_ref[0, 0, j * heads + h:j * heads + h + 1, :]
            if channel:
                v, b = row(0), row(1)
                a = kq[:, 2 * heads + h:2 * heads + h + 1]  # (dk, 1): a state row's
                Sk = jnp.sum(S * (a * k), axis=0, keepdims=True)  # (1, dv)
                Sq = jnp.sum(S * (a * q), axis=0, keepdims=True)
            else:
                v, a, b = row(0), row(1), row(2)
                Sk = a * jnp.sum(S * k, axis=0, keepdims=True)  # (1, dv)
                Sq = a * jnp.sum(S * q, axis=0, keepdims=True)
            d = b * (v - Sk)
            out_ref[0, h] = a * S + k * d
            # (k . q) d, summed down the sublanes
            o_ref[0, 0, h:h + 1, :] = Sq + jnp.sum((k * q) * d, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(pool, block, fresh, q, k, v, alpha, beta, *, interpret):
    """A `jax.jit` of its own, so that the linear layers of a step share one
    trace and one lowering of the kernel (as `paged_attention._per_device`)."""
    nblk, H, dk, dv = pool.shape
    B = q.shape[0]
    hb = _heads_a_step(H)
    G = H // hb
    valid = block < nblk
    # live rows first, in row order; the steps past them repeat the last one
    order = jnp.argsort(jnp.logical_not(valid), stable=True).astype(jnp.int32)
    n_live = jnp.sum(valid).astype(jnp.int32)[None]
    table = jnp.minimum(block, nblk - 1).astype(jnp.int32)
    # k and q down the sublanes: (B, G, dk, 128), lane h head h's k, lane hb + h its q
    columns = lambda a: jnp.swapaxes(a.reshape(B, G, hb, dk), 2, 3)
    channel = alpha.ndim == 3  # one alpha a state row: down the sublanes beside k and q
    down = [columns(k), columns(q)] + ([columns(alpha)] if channel else [])
    kq = jnp.concatenate(down, axis=-1)
    kq = jnp.pad(kq, [(0, 0)] * 3 + [(0, LANES - len(down) * hb)])
    rows = lambda a: jnp.broadcast_to(a.reshape(B, G, hb, 1), (B, G, hb, dv))
    along = [v.reshape(B, G, hb, dv)] + ([] if channel else [rows(alpha)]) + [rows(beta)]
    vab = jnp.concatenate(along, axis=2)

    def at(i, g, order_ref, table_ref, fresh_ref, live_ref):
        # the step's (row, group); past the last live row, the last live step's
        last = jnp.maximum(live_ref[0] - 1, 0)
        parked = i >= live_ref[0]
        return order_ref[jnp.where(parked, last, i)], jnp.where(parked, G - 1, g)

    def row_block(i, g, *refs):
        r, g = at(i, g, *refs)
        return (r, g, 0, 0)

    def state_block(i, g, *refs):
        r, g = at(i, g, *refs)
        return (refs[1][r], g, 0, 0)

    o, pool = pl.pallas_call(
        functools.partial(_kernel, heads=hb, channel=channel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, G),
            in_specs=[
                pl.BlockSpec((1, 1, dk, LANES), row_block),
                pl.BlockSpec((1, 1, len(along) * hb, dv), row_block),
                pl.BlockSpec((1, hb, dk, dv), state_block),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb, dv), row_block),
                pl.BlockSpec((1, hb, dk, dv), state_block),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, G, hb, dv), jnp.float32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # the pool is operand 6 (after the four scalar operands, kq and vab)
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY),
        ),
        interpret=interpret,
        name="paged_delta_step",
    )(
        order, table, fresh.astype(jnp.int32), n_live,
        kq, vab, pool,
    )
    # a row no step visited keeps what the buffer held: zeros instead
    o = jnp.where(valid[:, None, None], o.reshape(B, H, dv), 0.0)
    return o, pool


def paged_delta_step(pool, block, fresh, q, k, v, alpha, beta, *, interpret=None):
    """One token of the gated delta rule for every row that holds a state
    block. pool: (blocks, H, dk, dv) float32, donated by the caller's
    program and updated in place; block: (B,) int32, each row's state block
    (== blocks: the row holds none, its state is neither read nor written
    and its output is zero); fresh: (B,) bool, the row starts from a zero
    state; q, k: (B, H, dk), v: (B, H, dv), beta: (B, H), alpha: (B, H), one
    decay a head, or (B, H, dk), one a state row; float32. Returns (o (B, H,
    dv), the pool). The rule and its float32 arithmetic are
    `models.transformer._delta_step`'s."""
    if interpret is None:
        interpret = _interpret_default()
    return _call(pool, block, fresh, q, k, v, alpha, beta, interpret=interpret)
