"""Expert parallelism — MoE routing with all_to_all dispatch over ICI.

Completes the framework's parallelism quintet (dp/fsdp/tp/sp/**ep** —
SURVEY.md §2.3). The reference stack has no EP; the TPU-native design
follows the standard top-k token-choice recipe (Switch/GShard family):

* experts sharded over the ``ep`` mesh axis (each rank owns
  n_experts/ep_size experts);
* router computes top-k expert scores per token; tokens are packed into
  per-expert capacity buffers (static shapes — XLA requirement), dropped
  beyond capacity;
* `lax.all_to_all` moves token buffers to their expert's rank and back
  (the ICI-native form of the dispatch/combine collectives);
* everything is differentiable; router uses softmax gating with the
  load-balancing auxiliary loss from the Switch Transformer.

Entry points:
  * `dropless_moe(...)` — the DROPLESS layer of a patterned model
    (`models/transformer.py::SparseMoE`): every assignment is computed,
    grouped by expert, through a grouped SwiGLU (`grouped_swiglu`: the
    one Pallas kernel of `ops/grouped_mlp.py` where `grouped_kernel_ok`,
    `jax.lax.ragged_dot` elsewhere); told which experts it holds, it routes over all and
    returns its own experts' part. No capacity, no exchange (one chip
    holds what it is told it holds);
  * `moe_mlp(...)` — plain function usable inside any shard_map over an
    ``ep`` axis (what `dryrun_multichip` and the tests exercise);
  * `make_ep_moe(mesh, ...)` — jit-ready sharded wrapper;
  * the flax module form lives in `models/transformer.py` (`MoE`), wired
    in via `TransformerConfig(n_experts > 0)`.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

from jax.lax import axis_size as _axis_size


def _topk_routing(logits, n_experts: int, capacity: int, k: int = 1):
    """Token-choice top-k routing (Switch k=1, GShard/Mixtral k>1).

    Returns ((T, k) expert_idx, (T, k) gate, (T, k) position, (T, k) keep,
    aux_loss). Position = slot inside the expert's capacity buffer.
    Capacity is assigned choice-major (every token's 1st choice before any
    2nd choice — GShard's priority order), so over-capacity drops hit
    lower-priority choices first. Gates: k=1 keeps the raw softmax prob
    (Switch); k>1 renormalizes the top-k probs to sum to 1 (Mixtral).
    Aux is the Switch load-balance loss E * sum_e f_e * P_e with f_e the
    first-choice token fraction."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    topv, topi = lax.top_k(probs, k)  # (T, k)
    if k > 1:
        gate = topv / jnp.sum(topv, axis=-1, keepdims=True)
    else:
        gate = topv

    experts, positions, keeps = [], [], []
    offsets = jnp.zeros((n_experts,), jnp.int32)  # slots used by higher prio
    for j in range(k):
        onehot = jax.nn.one_hot(topi[:, j], n_experts, dtype=jnp.int32)
        pos_1b = offsets[None, :] + jnp.cumsum(onehot, axis=0)  # 1-based
        position = jnp.sum(pos_1b * onehot, axis=-1) - 1  # (T,) 0-based
        experts.append(topi[:, j])
        positions.append(position)
        keeps.append(position < capacity)
        offsets = offsets + jnp.sum(onehot, axis=0)

    expert = jnp.stack(experts, axis=1)  # (T, k)
    position = jnp.stack(positions, axis=1)
    keep = jnp.stack(keeps, axis=1)

    # Switch load-balance loss on the FIRST choice
    onehot1 = jax.nn.one_hot(topi[:, 0], n_experts, dtype=jnp.float32)
    frac_tokens = jnp.mean(onehot1, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(frac_tokens * frac_probs)
    return expert, gate, position, keep, aux


def moe_mlp(
    x,
    w_up,
    w_down,
    router_w,
    axis_name: Optional[str] = "ep",
    capacity_factor: float = 1.25,
    act: Optional[Callable] = None,
    k: int = 1,
):
    """Top-k MoE MLP (k=1 Switch, k>1 GShard/Mixtral). Inside shard_map:
    x (T_local, D) per rank, w_up/w_down the rank's LOCAL experts
    (E_local, D, F) / (E_local, F, D); router_w (D, E_global) replicated.
    Outside (axis_name=None): all experts local.

    Returns (y, aux_loss).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    act = act or jax.nn.gelu
    T, D = x.shape
    E_local = w_up.shape[0]
    if axis_name is not None:
        ep = _axis_size(axis_name)
    else:
        ep = 1
    E = E_local * ep

    logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)  # (T, E)
    capacity = max(1, int(capacity_factor * k * T / E))
    expert, gate, position, keep, aux = _topk_routing(logits, E, capacity, k)

    # scatter tokens into per-expert capacity buffers: (E, C, D) — each
    # token lands in up to k buffers (its top-k experts).
    # Global expert id is ep-group-major: expert e lives on rank e // E_local.
    buf = jnp.zeros((E, capacity, D), x.dtype)
    safe_pos = jnp.where(keep, position, 0)
    x_rep = jnp.repeat(x, k, axis=0)  # token-major (T*k, D): x[t] for each choice
    buf = buf.at[expert.reshape(-1), safe_pos.reshape(-1)].add(
        jnp.where(keep.reshape(-1, 1), x_rep, 0), mode="drop"
    )

    if axis_name is not None and ep > 1:
        # dispatch: send each expert group's buffers to its rank; receive
        # (src_rank, local_expert, C, D)
        buf = lax.all_to_all(
            buf.reshape(ep, E_local, capacity, D),
            axis_name, split_axis=0, concat_axis=0, tiled=False,
        )
        # expert compute, tokens from all source ranks batched per expert
        tokens = buf.transpose(1, 0, 2, 3).reshape(E_local, ep * capacity, D)
        h = act(jnp.einsum("ecd,edf->ecf", tokens, w_up))
        y = jnp.einsum("ecf,efd->ecd", h, w_down)  # (E_local, ep*C, D)
        y = y.reshape(E_local, ep, capacity, D).transpose(1, 0, 2, 3)
        # combine: route results back to the source ranks
        y = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0, tiled=False)
        y = y.reshape(E, capacity, D)  # this rank's tokens, by global expert
    else:
        h = jnp.einsum("ecd,edf->ecf", buf, w_up)
        h = act(h)
        y = jnp.einsum("ecf,efd->ecd", h, w_down)

    # gather back to token order, weighted gate-sum over the k choices
    out = (y[expert, safe_pos] * (gate * keep).astype(y.dtype)[:, :, None]).sum(
        axis=1
    )
    if axis_name is not None and ep > 1:
        aux = lax.pmean(aux, axis_name)  # replicated aux for the loss term
    return out.astype(x.dtype), aux


def grouped_kernel_ok(rows: int, d_in: int, d_mid: int, dtype) -> bool:
    """Whether `grouped_swiglu` runs the Pallas kernel of
    `ops/grouped_mlp.py`: THE predicate, from shapes and dtype alone: whole
    row tiles (the kernel has no ragged last one), an expert width the
    kernel has a tile for (`swiglu_tile`), and the 2-byte operands the
    tiles were measured with."""
    import jax.numpy as jnp

    from ..ops.grouped_mlp import ROW_TILE, swiglu_tile

    itemsize = jnp.dtype(dtype).itemsize
    return bool(
        rows % ROW_TILE == 0 and itemsize == 2
        and swiglu_tile(d_in, d_mid, itemsize)
    )


def grouped_swiglu(rows, w_gate, w_up, w_down, sizes):
    """SwiGLU of each group's rows through its own expert: `rows` (N, D)
    sorted by group, group g the next `sizes[g]` of them, through
    `w_gate[g]`, `w_up[g]` (D, F) and `w_down[g]` (F, D). Float32 (N, D);
    rows past the last group hold nothing to read.

    Where `grouped_kernel_ok`, ONE Pallas call a layer
    (`ops/grouped_mlp.py::grouped_swiglu_kernel`: only the groups that have
    rows, each expert's tiles streamed once, the SwiGLU kept in VMEM; a
    Mosaic call that keeps the caller's scope path in a device trace, which
    XLA's rewrite of `ragged_dot` on a TPU does not); elsewhere three
    `jax.lax.ragged_dot`. Both accumulate in float32 and cast the SwiGLU to
    the rows' dtype before the down product; both are differentiable."""
    from ..ops import grouped_mlp
    from ..ops.flash_attention import _interpret_default

    D, F = w_gate.shape[1:]
    if not grouped_kernel_ok(rows.shape[0], D, F, rows.dtype):
        return grouped_mlp.ragged_swiglu(rows, w_gate, w_up, w_down, sizes)
    return grouped_mlp.grouped_swiglu_kernel(
        rows, w_gate, w_up, w_down, sizes,
        grouped_mlp.swiglu_tile(D, F, rows.dtype.itemsize), _interpret_default(),
    )


def _share_of_assignments(x, order, sizes, weights, experts, top_k: int, few: int):
    """`dropless_moe`'s grouped products and their sum back onto the
    tokens for a caller that holds a SHARE of the experts: it is given
    about that share of the T * top_k assignments, and they lead the sorted
    `order` (the rest sort behind every held group). Where all of them lie
    in its first `few`, only those rows are gathered, multiplied and
    summed back (a one-hot product, `few` rows onto T tokens: exact in
    float32); a call that places more computes every assignment, as a chip
    that holds every expert does. Nothing is dropped either way, and the
    result does not depend on `few`. Returns (y (T, D) float32, stats)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, D = x.shape
    with jax.named_scope("dispatch"):
        total = jnp.sum(sizes)

    def grouped(n):
        with jax.named_scope("dispatch"):
            first = order[:n]
            rows = x[first // top_k]  # (n, D), by expert
            placed = jnp.arange(n) < total
        with jax.named_scope("experts"):
            out = grouped_swiglu(rows, *experts, sizes)  # float32
        with jax.named_scope("dispatch"):
            # rows past the last group belong to no product
            return jnp.where(placed[:, None], out, 0.0) * weights[first][:, None], first

    def leading():
        out, first = grouped(few)
        with jax.named_scope("dispatch"):
            onto = (first // top_k)[None, :] == jnp.arange(T)[:, None]  # (T, few)
            return jnp.dot(
                onto.astype(jnp.float32), out, precision=lax.Precision.HIGHEST
            )

    def every():
        out, _ = grouped(T * top_k)
        with jax.named_scope("dispatch"):
            back = jnp.zeros((T * top_k,), jnp.int32).at[order].set(
                jnp.arange(T * top_k, dtype=jnp.int32)
            )
            return out[back].reshape(T, top_k, D).sum(axis=1)

    y = lax.cond(total <= few, leading, every)
    with jax.named_scope("dispatch"):
        stats = jnp.stack([total, jnp.sum(sizes > 0)]).astype(jnp.int32)
    return y, stats


def dropless_moe(
    x,
    router_w,
    w_gate,
    w_up,
    w_down,
    *,
    n_experts: int,
    top_k: int,
    scale: float = 1.0,
    first_expert: int = 0,
    row_mask=None,
    score: str = "softmax",
    choice_bias=None,
    norm_eps: float = 1e-20,
):
    """Dropless top-k MoE over gated (SwiGLU) experts.

    x: (T, D). router_w: (D, n_experts), the router's FULL width.
    w_gate / w_up: (held, D, F), w_down: (held, F, D): the experts
    `first_expert .. first_expert + held - 1`, the contiguous range this
    caller holds. Every row scores all `n_experts` in float32 (`score`:
    "softmax" over them, or "sigmoid" of each: no expert's score depends
    on another's), takes its `top_k` (with `choice_bias` ((n_experts,)) the
    `top_k` largest of score + bias: the bias moves the CHOICE and no
    weight), and weighs them by their scores
    normalised to sum to 1 (a "sigmoid" router's over their sum plus
    `norm_eps`), times `scale`; the result is the part of
    sum_e w_e * SwiGLU_e(x) that the held experts give (all of it when
    all are held; the parts of disjoint ranges add up to the whole).
    Weights multiply expert OUTPUTS.

    Nothing is dropped and there is no capacity: the T * top_k
    assignments are sorted by expert and the three products run grouped
    (`grouped_swiglu`), so an expert costs the rows it was given and an
    expert no row chose is not read. A caller that holds a SHARE of the
    experts is given about that share of the assignments: where four
    times a uniform router's share (in whole row tiles) is fewer rows
    than T * top_k, `_share_of_assignments` gathers and multiplies only
    that many where they suffice and every one where they do not.

    `row_mask` ((T,) bool): rows that are False have NO assignment: they
    reach no expert, widen no group and count in no counter; their
    output is zero.

    Returns (y (T, D) in x's dtype, stats, chosen): stats is int32 (2,)
    = (assignments computed here, distinct held experts with >= 1 row);
    chosen is the (T, top_k) experts each row's router picked, masked
    rows included (a comparison with a reference counts routing flips
    from it; a program that does not fetch it pays nothing).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, D = x.shape
    held = w_gate.shape[0]
    with jax.named_scope("router"):
        logits = jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )  # (T, n_experts)
        def top(scores):  # (T, k) scores and experts
            if choice_bias is None:
                return lax.top_k(scores, top_k)
            _, chosen = lax.top_k(scores + choice_bias.astype(jnp.float32), top_k)
            return jnp.take_along_axis(scores, chosen, axis=-1), chosen

        if score == "sigmoid":
            top_p, top_e = top(jax.nn.sigmoid(logits))
            weight = scale * top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + norm_eps)
        else:
            top_p, top_e = top(jax.nn.softmax(logits, axis=-1))
            weight = scale * top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    with jax.named_scope("dispatch"):
        local = top_e - first_expert
        mine = (local >= 0) & (local < held)
        if row_mask is not None:
            mine &= row_mask[:, None]
        # group `held` is nowhere: sorted last, in no group's size
        group = jnp.where(mine, local, held).reshape(T * top_k)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    # four times what a uniform router would place here, in whole row tiles
    # of the grouped kernel
    from ..ops.grouped_mlp import ROW_TILE

    few = -(-4 * T * top_k * held // (n_experts * ROW_TILE)) * ROW_TILE
    if few < T * top_k:
        y, stats = _share_of_assignments(
            x, order, sizes, jnp.where(mine, weight, 0.0).reshape(T * top_k),
            (w_gate, w_up, w_down), top_k, few,
        )
        return y.astype(x.dtype), stats, top_e
    with jax.named_scope("dispatch"):
        rows = x[order // top_k]  # (T * k, D), by expert
        w_sorted = jnp.where(mine, weight, 0.0).reshape(T * top_k)[order]
        placed = jnp.arange(T * top_k) < jnp.sum(sizes)
    with jax.named_scope("experts"):
        out = grouped_swiglu(rows, w_gate, w_up, w_down, sizes)  # float32
    with jax.named_scope("dispatch"):
        # rows past the last group belong to no product: whatever the
        # grouped product left there is not a number to weigh
        out = jnp.where(placed[:, None], out, 0.0) * w_sorted[:, None]
        back = jnp.zeros((T * top_k,), jnp.int32).at[order].set(
            jnp.arange(T * top_k, dtype=jnp.int32)
        )
        y = out[back].reshape(T, top_k, D).sum(axis=1)
        stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(jnp.int32)
    return y.astype(x.dtype), stats, top_e


def make_ep_moe(
    mesh, axis_name: str = "ep", capacity_factor: float = 1.25, k: int = 1
):
    """jit-ready sharded MoE: global x (T, D), experts stacked (E, D, F)
    sharded over ``ep`` dim 0; tokens sharded over ``ep`` too."""
    import jax
    from jax.sharding import PartitionSpec as P

    jmesh = getattr(mesh, "jax_mesh", mesh)
    from .._compat import shard_map_fn

    fn = shard_map_fn(
        functools.partial(
            moe_mlp, axis_name=axis_name, capacity_factor=capacity_factor, k=k
        ),
        mesh=jmesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name), P()),
        out_specs=(P(axis_name), P()),
    )
    return jax.jit(fn)
