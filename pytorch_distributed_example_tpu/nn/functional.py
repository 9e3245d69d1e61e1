"""Differentiable collectives — `torch.distributed.nn.functional` parity.

Torch ships autograd-aware collective wrappers (`torch/distributed/nn/
functional.py`): `all_reduce` whose backward all_reduces the gradient,
`all_gather` whose backward reduce_scatters, `all_to_all` whose backward
runs the inverse all_to_all, etc. On TPU the natural home for these is
INSIDE the compiled step: each function here is an axis-name collective
for use under `shard_map` (or `pmap`) over a mesh axis, built on the XLA
collective primitives whose transpose rules give exactly the torch
gradient semantics — pinned by `tests/test_nn_functional.py` against
dense references:

  value                          gradient (torch semantics)
  all_reduce(SUM):  y = Σ_j x_j            dx_j = Σ_i ct_i   (all_reduce)
  all_gather:       y = concat_j x_j       dx_j = Σ_i ct_i[j] (reduce_scatter)
  reduce_scatter:   y_i = (Σ_j x_j)[i]     dx_j = concat_i ct_i (all_gather)
  broadcast(src):   y_i = x_src            dx_src = Σ_i ct_i, else 0
  all_to_all:       transpose of shards    inverse all_to_all
  all_to_all_single: single-tensor chunk exchange (same transpose)
  reduce(dst):      dst gets Σ_j x_j, rest keep x_j  dx_j = ct_dst (broadcast)
  gather(dst):      dst gets concat_j x_j  dx_j = ct[j] (scatter from dst)
  scatter(src):     y_i = x_src[i]         dx_src = concat_i ct_i (gather)

Driver-mode / eager DistTensor collectives (`distributed.py`) are NOT
differentiable — that matches torch, where only the `nn.functional`
variants carry autograd.
"""

from __future__ import annotations

from typing import Optional

from jax.lax import axis_size as _axis_size

from ..types import ReduceOp


def _resolve_op(op):
    if isinstance(op, str):
        return ReduceOp[op.upper()]
    return op


def all_reduce(x, op=ReduceOp.SUM, axis_name: str = "dp"):
    """Differentiable all_reduce over a mesh axis.

    SUM/AVG/PREMUL_SUM are linear — their transpose is another psum, so
    the backward is an all_reduce of the cotangent, matching torch.
    MAX/MIN route through pmax/pmin (forward-correct; use SUM-family ops
    when gradients must flow — torch's functional wrapper has the same
    practical restriction for non-sum reductions).
    """
    from jax import lax

    from ..types import lower_reduce_op

    op = _resolve_op(op)
    lowered = lower_reduce_op(op, axis_name)
    if lowered is not None:
        return lowered(x)
    if op == ReduceOp.PRODUCT:
        # log-abs-exp lowering keeps PRODUCT differentiable; sign handled
        # via parity of negatives. Exact zeros would make log() emit -inf
        # and the backward 0*inf=NaN, so zero positions are masked out of
        # the log and the result (and its gradient) forced to 0 there —
        # the same zero-grad-at-zero convention as the NCCL-style y/x form.
        import jax.numpy as jnp

        zero = x == 0
        any_zero = lax.psum(zero.astype(jnp.int32), axis_name) > 0
        safe = jnp.where(zero, jnp.ones_like(x), x)
        sign = lax.psum(jnp.where(safe < 0, 1, 0), axis_name) % 2
        mag = lax.psum(jnp.log(jnp.abs(safe)), axis_name)
        prod = jnp.where(sign == 1, -1.0, 1.0) * jnp.exp(mag)
        return jnp.where(any_zero, jnp.zeros_like(prod), prod)
    raise ValueError(f"unsupported differentiable reduce op {op}")


def all_gather(x, axis_name: str = "dp", axis: int = 0, tiled: bool = True):
    """Differentiable all_gather: every rank gets the concatenation along
    `axis` (tiled=True, torch's flat layout) or a new leading rank dim
    (tiled=False). Backward = reduce_scatter of the cotangent."""
    from jax import lax

    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str = "dp", axis: int = 0):
    """Differentiable reduce_scatter(SUM): rank i gets the i-th shard of
    the cross-rank sum. Backward = all_gather of the cotangent."""
    from jax import lax

    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_to_all(x, axis_name: str = "dp", split_axis: int = 0, concat_axis: int = 0):
    """Differentiable all_to_all: split `split_axis` W ways, exchange, and
    concatenate along `concat_axis`. Backward is the inverse all_to_all."""
    from jax import lax

    return lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


def broadcast(x, src: int = 0, axis_name: str = "dp"):
    """Differentiable broadcast: every rank gets rank `src`'s value.
    Backward accumulates the summed cotangent at `src` (zero elsewhere) —
    torch's `_Broadcast.backward` reduce-to-src semantics — which falls
    out of the transpose of the source-masked psum."""
    from jax import lax

    mask = (lax.axis_index(axis_name) == src).astype(x.dtype)
    return lax.psum(x * mask, axis_name)


def gather(x, dst: int = 0, axis_name: str = "dp", axis: int = 0):
    """Differentiable gather: rank `dst` gets the concatenation, others get
    zeros (torch returns tensors only at dst; SPMD needs a value on every
    rank — zeros keep the program shape-uniform). Backward routes each
    cotangent slice from dst back to its source rank."""
    from jax import lax

    full = lax.all_gather(x, axis_name, axis=axis, tiled=True)
    mask = (lax.axis_index(axis_name) == dst).astype(x.dtype)
    return full * mask


def scatter(x, src: int = 0, axis_name: str = "dp", axis: int = 0):
    """Differentiable scatter: rank i receives the i-th slice along `axis`
    of rank `src`'s input. Backward gathers cotangent slices to src."""
    from jax import lax

    full = broadcast(x, src, axis_name)  # replicate src's full tensor
    W = _axis_size(axis_name)
    if full.shape[axis] % W != 0:
        raise ValueError(
            f"scatter: dim {axis} of size {full.shape[axis]} not divisible "
            f"by axis {axis_name!r} size {W}"
        )
    i = lax.axis_index(axis_name)
    n = full.shape[axis] // W
    return lax.dynamic_slice_in_dim(full, i * n, n, axis=axis)


def reduce(x, dst: int = 0, op=ReduceOp.SUM, axis_name: str = "dp"):
    """Differentiable reduce-to-dst (torch `nn.functional.reduce`,
    `_Reduce`): rank `dst` receives the reduction; every other rank gets
    its INPUT back unchanged — torch's exact off-dst behavior (`_Reduce.
    forward` returns the in-place-reduced buffer, defined only at dst;
    ported code reading the off-dst value sees the input, not zeros).
    Backward is pinned by custom_vjp to `_Reduce.backward`'s semantics
    regardless of op: the cotangent AT dst broadcasts to every
    contributing rank; off-dst cotangents are discarded."""
    global _reduce_vjp
    if _reduce_vjp is None:  # built lazily: module import stays jax-free
        _reduce_vjp = _make_reduce_vjp()
    return _reduce_vjp(dst, _resolve_op(op), axis_name, x)


def _reduce_fwd(dst, op, axis_name, x):
    from jax import lax

    reduced = all_reduce(x, op, axis_name)
    keep = (lax.axis_index(axis_name) == dst).astype(x.dtype)
    return reduced * keep + x * (1 - keep), None


def _reduce_bwd(dst, op, axis_name, _res, ct):
    from jax import lax

    mask = (lax.axis_index(axis_name) == dst).astype(ct.dtype)
    return (lax.psum(ct * mask, axis_name),)


def _make_reduce_vjp():
    import jax

    f = jax.custom_vjp(
        lambda dst, op, axis_name, x: _reduce_fwd(dst, op, axis_name, x)[0],
        nondiff_argnums=(0, 1, 2),
    )
    f.defvjp(_reduce_fwd, _reduce_bwd)
    return f


_reduce_vjp = None


def all_to_all_single(x, axis_name: str = "dp", split_axis: int = 0,
                      concat_axis: int = 0):
    """torch `nn.functional.all_to_all_single` on the single-tensor
    layout: dim `split_axis` is split W ways, chunk i goes to rank i,
    received chunks concatenate along `concat_axis`. Even splits only
    (static shapes under jit); uneven sizes pad upstream — the eager
    `distributed.all_to_all_single` supports true uneven splits.
    Backward is the inverse all_to_all (self-transposing collective)."""
    from jax import lax

    W = _axis_size(axis_name)
    if x.shape[split_axis] % W != 0:
        raise ValueError(
            f"all_to_all_single: dim {split_axis} of size "
            f"{x.shape[split_axis]} not divisible by axis {axis_name!r} "
            f"size {W}; pad upstream (uneven splits live in the eager "
            "distributed.all_to_all_single)"
        )
    return all_to_all(x, axis_name, split_axis=split_axis,
                      concat_axis=concat_axis)
