"""Batched slot decode + chunked prefill — the serve engine's compiled
programs, refactored out of `models/generate.py`'s run-to-completion
loop into a continuous-batching step.

Hot-path discipline (this is what lets the per-token step compete with
`generate()`'s fused scan): ALL mutable serving state — the paged K/V
pool tree plus the per-slot (lengths, last-token, rng-key) vectors —
lives on DEVICE and is buffer-DONATED through every step, so the
multi-GB pool is updated in place instead of memcpy'd per token; the
only host traffic per step is the one next-token readback the
scheduler genuinely needs for EOS/budget retirement, and that is an
output of its own, so the engine can read it AFTER the next step has
taken the donated lanes (`ServeEngine.step`). Programs are
cached per (model, sampling knobs, mesh) exactly like
`generate._programs` (flax Modules are frozen dataclasses — hashable,
equal by config). `paged_programs` describes the quadruple.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Optional

from ..models.generate import sample_logits

__all__ = [
    "paged_programs",
    "kernel_layers",
    "layer_paths",
    "sync_slot_lanes",
    "carry_key",
]

_DECODE_PATH = "pytorch_distributed_example_tpu/serve/decode.py"


def carry_key(seed: int):
    """The post-first-token carry key as a PURE function of the seed —
    exactly what `first_token` leaves in the slot's rng lane after its
    one `split` (key = PRNGKey(seed); key, sub = split(key); sample
    with sub; carry key). Because the carry is seed-derived and never
    depends on device state, a DIFFERENT engine (the disagg decode
    pool, `serve/disagg/`) can reconstruct the in-flight RNG stream
    from the request metadata alone and continue sampling
    token-identically — migration never serializes device RNG lanes."""
    import jax

    return jax.random.split(jax.random.PRNGKey(seed))[0]


def _register_programs(family: str, **programs):
    """TDX_PROGLINT=1 register-on-compile seam: wrap each jitted serve
    program so its first call fingerprints the compiled collective
    sequence + donation set and (multiproc) agrees it across ranks
    before dispatch (`tools/proglint.py`). Off by default — the seam
    costs one env read per engine construction, nothing per step."""
    if os.environ.get("TDX_PROGLINT", "0") != "1":
        return tuple(programs.values())
    from ..tools import proglint

    return tuple(
        proglint.instrument(
            f"serve.{family}.{key}", fn, path=_DECODE_PATH
        )
        for key, fn in programs.items()
    )


def _kernel_partition(mesh, tp_axis: str):
    """The context a tp engine's paged programs apply the model under:
    the decode attention kernel is a Mosaic custom call GSPMD cannot
    partition, so `ops.partitioned_over(mesh, (), (tp_axis,))` makes it
    run per device on its KV-head shard. No mesh, no context."""
    if mesh is None:
        return contextlib.nullcontext()
    from ..ops import partitioned_over

    return partitioned_over(mesh, (), (tp_axis,))


def layer_paths(
    cache, rows: int, L: int, mesh=None, tp_axis: str = "tp"
) -> dict:
    """kind -> (layers of that kind, the path their mixer traces) when a
    program of `paged_programs(..., mesh, tp_axis)` applies the model to
    `rows` rows of `L` tokens (`step`: every slot, one token;
    `prefill_chunk`: one row, the chunk), for each kind of state `cache`
    (a `PagedKVCache`) holds. The kind's record names the path
    (`serve/kinds.py::Kind.path`): a Pallas kernel's ("decode_kernel",
    "chunk_kernel", "latent_decode_kernel", "latent_chunk_kernel",
    "recurrence_kernel") where `ops.paged_kernel` or `ops.delta_recurrence.
    delta_kernel_ok` says the kernel takes the call under the context the
    programs apply the model under, else the plain one's ("gather",
    "recurrence", "chunk_scan", "conv_step", "conv_chunk"); a linear layer
    whose decay is a vector a head says so before its form
    ("vector_recurrence_kernel", "vector_chunk_scan")."""
    cfg, tables = cache.model.cfg, cache.tables(slice(0, rows))
    if not isinstance(tables, tuple):
        tables = (tables,)
    with _kernel_partition(mesh, tp_axis):
        return {
            kind.name: (
                cache.layers[kind.name],
                kind.path(cfg, cache.avals[kind.name], table, L),
            )
            for kind, table in zip(cache.records, tables)
        }


def step_shares_blocks(cache, paths: dict, mesh=None, tp_axis: str = "tp") -> bool:
    """Whether the step whose `layer_paths` are `paths` reads a block that
    several of its rows' tables hold ONCE a group: every kind of its layers
    that reads `cache.block_tables` (`serve/kinds.py::Kind.shares` says
    which; a window layer has tables of its own and shares nothing) takes
    its decode kernel, and that kernel shares for the kind's pool
    (`ops.paged_attention.decode_shares`: THE predicate, asked under the
    context the programs apply the model under). The engine counts
    `StepRecord.decode_shared_keys` where this says so."""
    with _kernel_partition(mesh, tp_axis):
        said = [
            kind.shares(cache.avals[kind.name], paths[kind.name][1])
            for kind in cache.records if kind.name in paths
        ]
    said = [share for share in said if share is not None]
    return bool(said) and all(said)


def masks_padding(cfg) -> bool:
    """Whether the programs of a model of `cfg` are told which rows are real
    (`paged_programs`; the engine then pads a chunk with token id -1): a
    sparse layer's padding must route to no expert, and a kind of layer may
    say its padding is masked (`serve/kinds.py::Kind.masks_padding`)."""
    from .kinds import kinds_of

    return bool(getattr(cfg, "sparse_layers", ())) or any(
        kind.masks_padding for kind in kinds_of(cfg)
    )


def kernel_layers(paths: dict) -> int:
    """Of the layers `layer_paths` gave the paths of, those whose mixer
    traces a Pallas kernel (an attention layer one of
    `ops/paged_attention.py`, a linear layer `ops/delta_recurrence.py`'s)
    — the fact `ServeMetrics`' kernel counters name."""
    return sum(n for n, path in paths.values() if path.endswith("_kernel"))


def sync_slot_lanes(lengths, tokens, rngs):
    """Step-boundary quiesce — the serve DRAIN seam.

    Every per-slot state lane is buffer-donated through the compiled
    step, so "the step returned" does not mean "the device finished
    writing": a drain that serializes engine state while the last
    dispatch is still in flight would snapshot a boundary that never
    existed. Blocking on the lanes (the step's final outputs) orders
    the drain after everything the step wrote, pool included. The
    engine keeps one call's results unread (`ServeEngine.step`), so the
    drain seam has a second half: `ServeEngine.drain` first reads back
    and books every outstanding result (`ServeEngine.flush`), then
    blocks here — after this returns, the engine's host-side
    bookkeeping IS the state.
    Returns the same (lengths, tokens, rngs) triple, materialized."""
    import jax

    jax.block_until_ready((lengths, tokens, rngs))
    return lengths, tokens, rngs


@functools.lru_cache(maxsize=32)
def paged_programs(
    model, temperature: float, top_k: Optional[int], mesh=None,
    tp_axis: str = "tp",
):
    """(prefill_chunk, first_token, attach, step) jitted quadruple for
    the PAGED engine at the given sampling knobs.

    `mesh` (a `jax.sharding.Mesh`, the tp engine's) keys a quadruple of
    its own whose `prefill_chunk` and `step` apply the model under
    `_kernel_partition(mesh, tp_axis)` (a chunk of ONE token is a decode
    call to the model, so it takes the kernel too); everything else in
    the programs is partitioned by GSPMD from the operands' shardings,
    as before.

    The pool tree and the per-slot (lengths, last-token, rng)
    lanes are device-resident and DONATED through every program; block
    tables stay HOST-side numpy and ride in per call (tiny, mutated
    only at admission/growth/retire — see `serve/cache.py`).

    * ``prefill_chunk(params, tree, chunk (1, C), bt_row (1, nb),
      start)`` — one prompt chunk through the paged decode path at
      absolute offset `start`; returns (tree', logits (C, V)). The
      chunk's K/V scatter into the pool, then `ops.paged_chunk_attention`
      reads the row's pages up to `start + C` out of it (the gather +
      dense einsum over the table's span where `ops.paged_kernel` says
      no kernel takes the call: int8 pools, tiny heads, a window layer's
      chunk; `kernel_layers` counts which). Compiles
      once per CHUNK length C: with `prefill_chunk_tokens` set that is
      ONE program for every prompt; unchunked it is one per bucket. `start` is NONZERO both for later chunks of a
      long prompt and for the FIRST chunk after a prefix-cache attach
      (ISSUE 12): the engine hands the program a table row whose
      leading blocks hold another request's identical prompt prefix,
      and the chunk begins at the first uncached position — same RoPE
      absolute-position math, same causal mask over the row's logical
      layout, so a shared-prefix prefill is bit-identical to a cold
      one that happened to start there. Writes below `start` never
      occur (the engine copy-on-writes the boundary block before
      dispatch when it is shared).
    * ``first_token(chunk_logits, end, seed)`` — sample the request's
      first token from the TRUE prompt-end logits row (`end` indexes
      within the final chunk, so padding never leaks) with the
      per-request stream built from `seed` — mirrors `generate()`'s
      prefill rng discipline (one split consumed).
    * ``attach(lengths, tokens, rngs, slot, L, first, key)`` — fuse the
      finished request's state lanes into the donated slot vectors (the
      block table row was already built host-side chunk by chunk).
    * ``step(params, tree, lengths, tokens, rngs, bt)`` — advance EVERY
      slot one token through the paged attention path; returns (tree',
      lengths', tokens', rngs', readback). The first four are the next
      step's donated inputs. `readback` is the step's one host read,
      int32 (S,) = the next tokens again in a buffer no later program
      takes, so the engine may dispatch the next step (or an `attach`)
      before it reads this one. The write
      scatters into the pool, then `ops.paged_decode_attention` reads
      each row's pages out of it (the gather + dense einsum where
      `ops.paged_kernel` says the kernel cannot take the shape or
      the pool is int8). Compiles ONCE for the engine's lifetime;
      retired/prefilling slots ride along as parked lanes whose table
      rows are all-invalid, so their garbage writes are scatter-DROPPED
      (never in any live block), the kernel reads no page for them, and
      their sampled tokens are ignored by the scheduler.

    Where the layers keep more than one kind of state (`serve/cache.py`:
    every key and value, a window of them, a recurrent state, a latent
    row a token), `bt_row`
    and `bt` are the tuple of one table a kind, in `cfg.cache_kinds`'
    order.

    A model with SPARSE layers (`cfg.sparse_layers`) is told which rows
    are real, because a row that is not must route to no expert, and so
    is a model with layers of a kind that masks padding (`serve/kinds.py::
    Kind.masks_padding`: those that keep a state block, which padding must
    leave as the row's last token left it): in
    `prefill_chunk` the engine pads a chunk with token id -1 (the rows
    `chunk >= 0` are real; the padding embeds as token 0), in `step` a
    row is live when its table row holds a valid block (the engine hands
    parked and mid-prefill lanes over all-invalid; the layers that keep
    a state block need no telling there: an invalid block drops the write).
    Its `step`'s readback is longer, int32 (S + 2 * sparse layers,):
    the next tokens, then per sparse layer (assignments computed,
    distinct experts with a row) — the counters ride the transfer the
    scheduler makes anyway.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    M = model.cfg.max_seq_len
    sparse = tuple(getattr(model.cfg, "sparse_layers", ()))
    masked = masks_padding(model.cfg)

    def apply_paged(params, tree, tokens, positions, bt, row_mask=None):
        kw, mutable = {}, ["cache"]
        if masked:
            kw = {"row_mask": row_mask}
        if sparse:
            mutable = ["cache", "intermediates"]
        with _kernel_partition(mesh, tp_axis):
            return model.apply(
                {"params": params, "cache": tree}, tokens, decode=True,
                positions=positions, block_tables=bt, mutable=mutable, **kw,
            )

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_chunk(params, tree, chunk, bt_row, start):
        row_mask = None
        if masked:
            row_mask, chunk = chunk >= 0, jnp.maximum(chunk, 0)
        logits, vars2 = apply_paged(
            params, tree, chunk, jnp.asarray(start, jnp.int32)[None], bt_row,
            row_mask,
        )
        return vars2["cache"], logits[0]  # (C, V)

    @jax.jit
    def first_token(chunk_logits, end, seed):
        last = lax.dynamic_index_in_dim(
            chunk_logits, end, axis=0, keepdims=False
        )
        with jax.named_scope("sample"):
            key = jax.random.PRNGKey(seed)
            key, sub = jax.random.split(key)
            first = sample_logits(last[None], sub, temperature, top_k)[0]
        return first, key

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def attach(lengths, tokens, rngs, slot, length, first, key):
        return (
            lengths.at[slot].set(length),
            tokens.at[slot].set(first),
            rngs.at[slot].set(key),
        )

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4))
    def step(params, tree, lengths, tokens, rngs, bt):
        """One paged continuous-batching decode step over all S slots.

        lengths: (S,) int32 current depths (= this step's write
        positions); tokens: (S,) last emitted; rngs: (S, 2) per-slot
        keys; bt: (S, nb) block tables. Returns
        (tree', lengths', next_tokens (S,), rngs', readback). Parked lanes clamp
        at M-1 (in-bounds RoPE/mask); their invalid table rows drop the
        write and give the decode attention kernel no page to read."""
        with jax.named_scope("sample"):
            split = jax.vmap(jax.random.split)(rngs)  # (S, 2, 2)
            subs, new_rngs = split[:, 0], split[:, 1]
        row_mask = None
        if sparse:
            # a live row holds a block; any layer's kind of table says so:
            # layer 0's, whose every leaf leads with its pool's blocks
            kinds = model.cfg.cache_kinds
            kind = model.cfg.layers[0].attention
            table = bt[kinds.index(kind)] if isinstance(bt, (tuple, list)) else bt
            blocks = jax.tree_util.tree_leaves(tree["layers_0"])[0].shape[0]
            row_mask = jnp.any(table < blocks, axis=1, keepdims=True)
        logits, vars2 = apply_paged(
            params, tree, tokens[:, None], lengths, bt, row_mask
        )
        lg = logits[:, -1]  # (S, V)
        # sample_logits branches on the Python temperature at trace time
        # (greedy at 0.0, keys trace away), so one vmap covers both modes
        with jax.named_scope("sample"):
            nxt = jax.vmap(
                lambda row, key: sample_logits(row, key, temperature, top_k)
            )(lg, subs)
        stats = [
            vars2["intermediates"][f"layers_{i}"]["mlp"]["moe_stats"][0]
            for i in sparse
        ]
        return (
            vars2["cache"],
            jnp.minimum(lengths + 1, M - 1),
            nxt,
            new_rngs,
            jnp.concatenate([nxt.astype(jnp.int32), *stats]),
        )

    return _register_programs(
        "paged",
        prefill_chunk=prefill_chunk,
        first_token=first_token,
        attach=attach,
        step=step,
    )
