"""The sparse MLP's three grouped products as ONE Pallas TPU kernel
(`grouped_swiglu_kernel`): for rows sorted by group, `silu(x @ gate_g) *
(x @ up_g) @ down_g` through each group's own expert, an expert no row chose
never read. `parallel/expert_parallel.py::grouped_swiglu` is its only caller
and `grouped_kernel_ok` there the one predicate; `swiglu_tile` here says the
tile of the expert width a shape runs at (None: no tile, the `ragged_dot`
form).

Shape of the kernel (design per /opt/skills/guides/pallas_guide.md):

* Grid (visits, tiles of the expert width F), F innermost. A visit is one
  (group that HAS rows, row tile of `ROW_TILE` rows it reaches into): a group
  that straddles row tiles is visited once a tile, a tile that holds several
  groups once a group, consecutively, with the other groups' rows masked at
  the write (`_visits`: the work list, computed ONCE a layer in a handful of
  XLA fusions and prefetched as scalars; the grid's first extent is the
  number of visits the sizes give, so nothing runs for a group without rows).
* A step of (visit, f) has `w_gate[g][:, f]`, `w_up[g][:, f]` (D x tf) and
  `w_down[g][f, :]` (tf x D) copied in by the pipeline, each ONE copy, double
  buffered; the row tile of x and the float32 (rows, D) output tile stay
  resident over f and over consecutive visits of one row tile. The products
  take 2-byte operands into float32, the SwiGLU is float32 in VMEM, cast to
  the rows' dtype exactly where the `ragged_dot` form casts it, and
  `h @ down` is added into the output tile, the group's rows only. The gate
  and up results never reach HBM.
* Where F is ONE tile, consecutive visits of one group (a straddle) keep the
  expert's block indices, and the pipeline copies nothing for the second.
* Rows past the last group belong to no visit: what the output holds there
  is not a number to read (the caller masks them, as it did around `gmm`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of x a visit multiplies: one group's part of them is kept
ROW_TILE = 128
_NN = (((1,), (0,)), ((), ()))


# The widest copy of a gate / up tile the kernel asks for: at 8 MiB the
# experts of three of the four configurations below are ONE tile each.
WIDEST_COPY = 8 << 20


def swiglu_tile(d_in: int, d_mid: int, itemsize: int):
    """The tile `tf` of the expert width `d_mid` (F) that the kernel streams
    experts of `d_in` x `d_mid` at, from the shapes alone: the widest
    multiple of 256 that divides F whose (d_in, tf) copy is at most
    `WIDEST_COPY`, so the whole expert where that fits; None where there is
    no such tile (a width that is not whole tiles of 256, a `d_in` that is
    not whole lane tiles).

    Measured on a TPU v5 lite (PERF.md section 5, PR 44; bfloat16, 128-row
    tiles, us a layer of 16 chained ones and GB/s of the hit experts'
    weights; in brackets the parent's three `megablox.gmm` calls):
    256 experts of 2048 x 512, 256 rows over 164 of them: tf 256 / 512:
    1426 / 1434 us (724 / 720 GB/s) [1726]; 4096 rows over 256: 2474 / 2367
    [3059]. 16 of 2048 x 1792, 128 of 256 rows placed: tf 256 / 896 / 1792:
    520 / 525 / 529 [649 with a 152 us copy of the harness's own]; 1024 of
    2048 placed: 735 / 740 / 647 [866]. 64 of 3584 x 1024, 128 rows over 56:
    tf 256 / 512 / 1024: 1791 / 1697 / 1703 [2067]; 2048 rows over 64: 2512 /
    2374 / 2172 [2909]. 8 of 7680 x 2048, 128 of 512 rows placed: tf 128 /
    256 / 512: 1082 / 1115 / 1083 [1326]. A step does not care (the copies
    are a megabyte or more at every one); a chunk wants the WHOLE expert:
    where F is one tile a group that straddles two row tiles is copied
    once. Row tiles of 16 / 32 / 64 / 256 read within 1 % of 128 in a step
    and 1-10 % slower in a chunk."""
    if d_in % 128 or d_mid % 256:
        return None
    return max(
        (t for t in range(256, d_mid + 1, 256)
         if d_mid % t == 0 and d_in * t * itemsize <= WIDEST_COPY),
        default=None,
    )


def vmem_limit(d_in: int, tf: int, itemsize: int) -> int:
    """Scoped VMEM the call states: the three weight tiles twice (the
    pipeline's two buffers), the row tile and the float32 output tile twice,
    and an eighth and 2 MiB of room for the step's own values (the compiler
    counts 0.7-3.6 MiB of them at the four configurations' shapes: 15.7 MiB
    in all at 2048 x 512, 46.3 at 2048 x 1792, 48.6 at 3584 x 1024, 59.9 at
    tiles of 512 of 7680 x 2048, of the 128 MiB a v5e core has); never under
    the 16 MiB a kernel gets unasked."""
    buffers = 2 * 3 * d_in * tf * itemsize + 2 * ROW_TILE * d_in * (itemsize + 4)
    return max(16 << 20, buffers * 9 // 8 + (2 << 20))


def _visits(sizes, rows: int):
    """The work list of a call over `rows` rows in groups of `sizes`:
    (offsets (G + 1,), group (V,), row tile (V,), count) with V = rows /
    ROW_TILE + G - 1, the most visits any sizes give. Visit v < count is the
    `v - first[g]`-th row tile of the g-th group, groups in order and each
    group's tiles in order, so the visits of one row tile are consecutive;
    the grid ends at `count`, and the entries past it name the last group
    and a row tile that exists, whoever looks ahead."""
    G, tm = sizes.shape[0], ROW_TILE
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    upto = jnp.cumsum(tiles)  # visits of groups 0..g
    v = jnp.arange(rows // tm + G - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(v[:, None] >= upto[None, :], axis=1), G - 1).astype(jnp.int32)
    tile = first_tile[group] + v - (upto - tiles)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group,
            jnp.clip(tile, 0, rows // tm - 1).astype(jnp.int32), upto[-1])


def _dot(a, b):
    # one pass over 2-byte operands into float32, exact; an ambient
    # `jax_default_matmul_precision` (the test harness pins "highest") would
    # ask Mosaic for a float32 contraction of them, which it refuses
    return lax.dot_general(
        a, b, _NN, precision=lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )


def _kernel(offsets, group, tile, x_ref, gate_ref, up_ref, down_ref, out_ref, *, nf):
    v, f = pl.program_id(0), pl.program_id(1)
    x = x_ref[...]
    h = jax.nn.silu(_dot(x, gate_ref[...])) * _dot(x, up_ref[...])
    part = _dot(h.astype(x.dtype), down_ref[...])  # (ROW_TILE, D) float32
    g = group[v]
    row = tile[v] * ROW_TILE + lax.broadcasted_iota(jnp.int32, part.shape, 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])

    @pl.when(f == 0)
    def _first():
        out_ref[...] = jnp.where(mine, part, out_ref[...])

    if nf > 1:
        @pl.when(f > 0)
        def _add():
            out_ref[...] = jnp.where(mine, out_ref[...] + part, out_ref[...])


@functools.partial(jax.jit, static_argnames=("tf", "interpret"))
def _call(rows, w_gate, w_up, w_down, sizes, *, tf, interpret):
    N, D = rows.shape
    F = w_gate.shape[2]
    offsets, group, tile, count = _visits(sizes, N)
    x_spec = pl.BlockSpec((ROW_TILE, D), lambda v, f, o, g, t: (t[v], 0))
    in_spec = pl.BlockSpec((None, D, tf), lambda v, f, o, g, t: (g[v], 0, f))
    down_spec = pl.BlockSpec((None, tf, D), lambda v, f, o, g, t: (g[v], f, 0))
    G = w_gate.shape[0]  # the experts hit, at most: the estimate is from shapes
    return pl.pallas_call(
        functools.partial(_kernel, nf=F // tf),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(count, F // tf),
            in_specs=[x_spec, in_spec, in_spec, down_spec],
            out_specs=x_spec,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(D, tf, rows.dtype.itemsize),
        ),
        cost_estimate=pl.CostEstimate(
            flops=6 * N * D * F, transcendentals=N * F,
            bytes_accessed=(3 * G * D * F + N * D) * rows.dtype.itemsize + 4 * N * D,
        ),
        interpret=interpret,
        name="grouped_swiglu",
    )(offsets, group, tile, rows, w_gate, w_up, w_down)


def ragged_swiglu(rows, w_gate, w_up, w_down, sizes):
    """The same layer through `jax.lax.ragged_dot`: what the kernel is held
    against, what runs where no tile fits, and whose VJP the kernel's is."""
    dot = lambda a, w: lax.ragged_dot(a, w, sizes, preferred_element_type=jnp.float32)
    h = jax.nn.silu(dot(rows, w_gate)) * dot(rows, w_up)
    return dot(h.astype(rows.dtype), w_down)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def grouped_swiglu_kernel(rows, w_gate, w_up, w_down, sizes, tf, interpret):
    """Float32 (N, D): SwiGLU of each group's rows through its own expert
    (`rows` (N, D) sorted by group, N whole tiles of `ROW_TILE`, group g the
    next `sizes[g]`; `w_gate`, `w_up` (G, D, F), `w_down` (G, F, D); `tf` a
    divisor of F in whole lane tiles). Differentiable: the backward is the
    VJP of the `ragged_dot` form."""
    return _call(rows, w_gate, w_up, w_down, sizes, tf=tf, interpret=interpret)


def _fwd(rows, w_gate, w_up, w_down, sizes, tf, interpret):
    out = _call(rows, w_gate, w_up, w_down, sizes, tf=tf, interpret=interpret)
    return out, (rows, w_gate, w_up, w_down, sizes)


def _bwd(tf, interpret, saved, ct):
    *operands, sizes = saved
    _, vjp = jax.vjp(lambda *a: ragged_swiglu(*a, sizes), *operands)
    return (*vjp(ct), None)


grouped_swiglu_kernel.defvjp(_fwd, _bwd)
