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
