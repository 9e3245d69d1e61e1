"""The kinds of layer the serve plane carries: ONE record a kind.

What a layer keeps between calls is the model's to say
(`models/transformer.py::cache_leaves`: its mixer's name, how the state
grows, its leaves). What the POOL and the ENGINE do with it is said here,
once, and asked by everyone: `serve/cache.py` builds and counts a layer's
pool from `Kind.pool` and hands it the table of `Kind.table`;
`serve/decode.py` reports `Kind.path` and `Kind.shares` and tells a row's
padding where `Kind.masks_padding`; `serve/engine.py` refuses what
`Kind.not_carried` names. No other module of `serve/` names a kind: a new
one is its mixer and its leaves in `models/transformer.py`, its kernel
under `ops/` if it has one, and a record below.

The pool has three families of table, and a kind rides one of them:

* ``"blocks"`` — `PagedKVCache.block_tables`: a block a `block_size` tokens,
  allocated on write, refcounted, shareable between requests (the prefix
  cache) and copied on write; every token is kept until the request retires.
* ``"window"`` — `window_tables`: the same shape from a small pool of its
  own; a block wholly behind `position - cfg.window` goes back to the free
  list while the request runs.
* ``"state"`` — `state_table`: ONE block a request from `allocate()` to
  `free()`, whatever its length, the same block index in every layer that
  rides the family.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from ..models.transformer import CACHE_KINDS, layers_of
from ..ops import paged_kernel
from ..ops.delta_recurrence import delta_kernel_ok
from ..ops.paged_attention import decode_shares, pool_kv_shape, pool_latent_width

__all__ = ["FAMILIES", "KINDS", "Kind", "kinds_of"]

FAMILIES = ("blocks", "window", "state")


def _kv_pool(leaves, blocks, block_size, quantized):
    """K and V as the pool holds them (`ops.pool_kv_shape`: KV heads in
    whole sublane tiles, 64-wide heads two a row); `quantized` stores them
    as int8 beside one float32 scale a (token, held head),
    `<leaf>_scale`."""
    avals = {}
    for leaf, (shape, dtype) in leaves.items():
        heads, width = pool_kv_shape(*shape)
        avals[leaf] = jax.ShapeDtypeStruct(
            (blocks, block_size, heads, width), jnp.int8 if quantized else dtype
        )
        if quantized:
            avals[f"{leaf}_scale"] = jax.ShapeDtypeStruct(
                (blocks, block_size, heads), jnp.float32
            )
    return avals


def _latent_pool(leaves, blocks, block_size, quantized):
    """One row a token, in whole lane tiles past 128 values
    (`ops.pool_latent_width`: 576 are held as 640)."""
    if quantized:
        raise ValueError("a latent pool has no int8 form")
    return {
        leaf: jax.ShapeDtypeStruct(
            (blocks, block_size, pool_latent_width(*shape)), dtype
        )
        for leaf, (shape, dtype) in leaves.items()
    }


def _state_pool(leaves, blocks, block_size, quantized):
    """A state block is its mixer's leaves as they are, one entry a block."""
    return {
        leaf: jax.ShapeDtypeStruct((blocks,) + shape, dtype)
        for leaf, (shape, dtype) in leaves.items()
    }


def _kv_path(windowed: bool):
    def path(cfg, avals, table, L):
        kernel = paged_kernel(L, avals["k"], table, cfg.window if windowed else None)
        return f"{kernel}_kernel" if kernel else "gather"

    return path


def _latent_path(cfg, avals, table, L):
    kernel = paged_kernel(L, avals["latent"], table, rank=cfg.latent_kv_rank)
    return f"{kernel}_kernel" if kernel else "gather"


def _linear_path(cfg, avals, table, L):
    """The form of the recurrence, "vector_" before it where the decay is a
    vector a head (`cfg.linear_decay == "channel"`)."""
    form = "vector_" if cfg.linear_decay == "channel" else ""
    if L > 1:
        return f"{form}chunk_scan"
    kernel = "_kernel" if delta_kernel_ok(avals["state"]) else ""
    return f"{form}recurrence{kernel}"


def _conv_path(cfg, avals, table, L):
    return "conv_step" if L == 1 else "conv_chunk"


def _shares_nothing(avals, path):
    return None


@dataclass(frozen=True)
class Kind:
    """What the serve plane does with the layers of one kind.

    `table`: the family of table its layers are handed (`FAMILIES`).
    `gauge`: the name `/serve`'s `cache_pool` counts its blocks under.
    `pool(leaves, blocks, block_size, quantized)`: leaf ->
    `ShapeDtypeStruct` of ONE layer's subtree as the pool holds it, from
    the leaves its mixer names; the tree, the bytes a block pins and every
    question about the pool's shape come from this.
    `path(cfg, avals, table, L)`: the path its mixer traces for `L` query
    tokens a row of `table` over the pool `avals`, as `layer_paths` names
    it (asked under the context the programs apply the model under).
    `shares(avals, path)`: whether a decode step that takes `path` reads a
    block several rows hold ONCE; None for a kind that does not read
    `block_tables`.
    `masks_padding`: its programs are told which rows are real, because
    padding must leave a row's block as its last token left it.
    `not_carried`: feature of the engine -> why it is refused with layers
    of this kind."""

    name: str
    table: str
    gauge: str
    pool: Callable
    path: Callable
    shares: Callable = _shares_nothing
    masks_padding: bool = False
    not_carried: Mapping[str, str] = field(default_factory=dict)


_STATE_NOT_CARRIED = {
    "prefix_cache": "a shared prefix's recurrent state is not snapshotted at "
                    "the prefix's end",
    "kv_quant": "an int8 pool beside float32 state blocks is untested",
    "mesh": "the state pool and the recurrence are not partitioned over tp",
    "role": "block migration moves K/V blocks, not a state block",
    "precompiled": "pre-warmed programs take one table",
}

KINDS: Dict[str, Kind] = {kind.name: kind for kind in (
    Kind(
        "full", "blocks", "full", _kv_pool, _kv_path(windowed=False),
        shares=lambda avals, path: (
            path == "decode_kernel" and decode_shares(avals["k"])
        ),
    ),
    Kind(
        "window", "window", "window", _kv_pool, _kv_path(windowed=True),
        not_carried={
            "prefix_cache": "a shared prefix's window-layer blocks are "
                            "recycled under its other holders",
            "kv_quant": "an int8 pool of two kinds of blocks is untested",
            "mesh": "the window pool and the windowed decode kernel are not "
                    "partitioned over tp",
            "role": "block migration moves one kind of block",
            "precompiled": "pre-warmed programs take one table",
        },
    ),
    Kind(
        "linear", "state", "state", _state_pool, _linear_path,
        masks_padding=True, not_carried=_STATE_NOT_CARRIED,
    ),
    Kind(
        "latent", "blocks", "latent", _latent_pool, _latent_path,
        shares=lambda avals, path: (
            path == "latent_decode_kernel" and decode_shares(avals["latent"])
        ),
        not_carried={
            "kv_quant": "a latent pool has no int8 form",
            "mesh": "a latent pool has no KV heads to partition over tp",
            "role": "block migration moves K/V blocks, not latent ones",
            "precompiled": "pre-warmed programs take a K/V pool",
        },
    ),
    Kind(
        "conv", "state", "state", _state_pool, _conv_path,
        masks_padding=True, not_carried=_STATE_NOT_CARRIED,
    ),
)}
assert tuple(KINDS) == CACHE_KINDS, "one record a kind, in the model's order"


def kinds_of(cfg) -> Tuple[Kind, ...]:
    """The records of the kinds `cfg`'s layers are of, in `CACHE_KINDS`'
    order: the order the programs take their tables in."""
    have = set(layers_of(cfg))
    return tuple(kind for kind in KINDS.values() if kind.name in have)
