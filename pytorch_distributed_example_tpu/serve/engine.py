"""ServeEngine — continuous-batching scheduler over the paged KV cache.

The serving loop the ROADMAP's "heavy traffic" north star needs:
requests enter a bounded queue (`serve/queue.py`), get admitted into
cache SLOTS whose memory is paged from a shared block pool
(`serve/cache.py` — allocated on write, freed at retire, so HBM per
request tracks live tokens), are prefilled in CHUNKS interleaved with
decode (`prefill_chunk_tokens` bounds how much prompt work any single
step may do, so a burst of long prompts cannot freeze in-flight
decodes or starve short requests' TTFT), and then EVERY decoding slot
advances one token per `step()` through the single compiled paged
decode program (`serve/decode.py`). Retirement frees the slot AND its
blocks and admission backfills MID-STREAM — no run-to-completion
barrier.

One call's device work is always in flight: `step()` dispatches its
own programs and only then reads back what the previous call
dispatched, so the device never waits for the host's bookkeeping and
the host's one round-trip a token is hidden behind the next step
(`step`'s docstring has the contract: what a call returns, what reads
everything back first, the `pipeline` counters).

Pool pressure resolves by PREEMPTION, youngest-request-first: when a
slot must grow into a block and the pool is dry, the youngest active
request (possibly the grower itself) is evicted — blocks freed, request
requeued at the head — and replays later from its own seed,
token-identically. `submit()` refuses requests whose WORST-CASE
footprint exceeds the whole pool, which makes the preemption loop
deadlock-free: the oldest request can always claim enough blocks to
finish. Admission additionally waits until the pool can hold a
request's first chunk, so nothing thrashes at the door.

``kv_quant=True`` switches the pool to the INT8 cache
(`serve/cache.py` quantized mode): ~4x the blocks per pool byte (minus
the per-(token, kv-head) scale overhead), quantize-on-scatter in the
paged write, dequant-in-gather so decode math is unchanged — at fixed
pool bytes this roughly doubles the concurrently servable requests.
Scheduling, preemption,
and replay are dtype-blind: a preempted quantized request replays
token-identically because quantization is deterministic.

Tensor-parallel decode: pass ``mesh=`` (a `DeviceMesh`/`jax.sharding.
Mesh` with a ``tp`` axis) and the engine places params per
`models.transformer.sharding_rules`, the block pool KV-head-sharded
(`parallel.tensor_parallel.shard_kv_pool`), and the slot lanes
replicated — the SAME jitted programs then run SPMD, with GSPMD
inserting the one all-reduce per block pair that Megatron hand-codes.
Slot bookkeeping and block tables stay host-side and identical on
every chip.

Prefix sharing (``prefix_cache=True``, ISSUE 12): admission looks the
request's prompt up in a radix prefix index (`serve/prefix.py`) and
ATTACHES the longest cached prefix's blocks (refcounted, `serve/
cache.py::attach_prefix`) so chunked prefill starts at the first
uncached position — skipping both the prefill compute and the pool
writes for every hit (a request that matched nothing looks again before
its first chunk, `_attach_late`: arrivals behind a head that another
request is still prefilling wait their turn and attach it, where each
would have computed it). Prompt blocks are indexed at prefill completion
(pristine — decoded tokens are never indexed); divergence inside a
shared or indexed block copies exactly that block (copy-on-write)
before the write. Sharing crosses TENANTS only when the request's
`ClassSpec.share_prefix` opts in (default off — each tenant gets a
private scope); pool-pressure and class-aware eviction only ever
DECREMENT refcounts, so a shared prefix survives its victims, and
unreferenced index entries are reclaimed LRU behind the plain free
list. Outputs are token-exact with sharing on or off: a cached block
holds exactly the K/V the attaching request would have recomputed
(same tokens, same absolute positions, same params).

Layer patterns (`TransformerConfig.layers`): what the serve plane does
with each kind of layer a pattern may hold is ONE record in
`serve/kinds.py` — which family of table its layers ride (`serve/cache.py`:
the refcounted blocks that keep every token; a window pool of its own,
sized here from `slots`, the window, `prefill_chunk_tokens` and
`block_size`, whose blocks behind `position - window` are recycled while
the request runs; one state block a request from admission to retirement),
whether its chunk padding is told apart from tokens (a state block must
stay as the last real token left it; a chunk that starts at position 0
reads a zero state, so preemption frees the block and the requeued request
prefills again from 0: no snapshot is kept), and which of prefix sharing,
the int8 pool, a tp mesh, disaggregated roles and pre-warmed executables
are NOT carried with it and why: those are refused at construction, in
the record's words. A model
with SPARSE (dropless MoE) layers has its parked lanes and chunk padding
route nowhere, and every decode step brings back, in its one readback,
the assignments computed and the distinct experts hit per sparse layer
(`ServeMetrics.record_moe_step`, `StepRecord.moe` and a `serve:moe_step`
host annotation, all written when the step is read back).

What a call did (`StepRecord`, public as `engine.last_step` when the
call returns): `step()` fills one small record as it goes, from values
it computes anyway — what it admitted, the chunks and the decode step
it dispatched, what it read back, booked and retired, and the host
seconds of each phase by the engine's clock. The same record is written
onto the host line of a profiler trace, as `jax.profiler.
TraceAnnotation`s that share the device trace's clock and cost nothing
without one: a `serve:step` span a call with the phases nested inside
(`serve:admit`, `serve:gauges`, `serve:prefill_tick`,
`serve:decode_tick`, `serve:wait`, `serve:book`), every dispatch under
its own span (`serve:decode_step`: rows, keys attended, `shared`: those
of them the kernel reads once for several rows;
`serve:prefill_chunk`: slot, start, tokens, bucket), a zero-length
`serve:step_done` that carries the record's counts, and a zero-length
`serve:admitted` an admission (prompt tokens, tokens attached from the
prefix index, microseconds queued: the values the record adds up a
call). What a call's annotations say is what its record holds; nothing
in the engine reads one back, asks whether a trace is running, or does
anything differently when one is. A `flush()` between two calls writes
its `serve:wait`, `serve:book` and `serve:step_done` under no
`serve:step`.

Fault surface: `serve.admit` before each admission, `serve.
prefix_attach` before a prefix-cache attach, `serve.prefill_chunk`
before each prompt chunk, `serve.step` before each decode batch,
`serve.drain` before a drain snapshot (all in `faults.KNOWN_POINTS`).
Transient faults requeue the affected requests at the queue head and
the engine carries on; because each request replays from its own seed,
a greedy request's output is token-identical across any number of
mid-stream requeues (`tests/test_serve.py` / `tests/test_serve_paged.py`
chaos cases), and a replayed request re-attaches its cached prefix
deterministically (`tests/test_serve_prefix.py`).

Multi-tenant SLO-aware admission (``classes=``): requests carry a
tenant id and a priority class; the queue admits by smooth weighted
round-robin across classes and, under a full queue, sheds the WORST
class present instead of collapsing FIFO (see `serve/queue.py`).
Cross-class preemption (`class_preemption=True`, the default when
classes are configured) lets waiting higher-priority work evict the
youngest in-flight request of a strictly worse class — the evictee
requeues and replays token-identically off its seed, exactly like a
pool-pressure preemption — and pool-pressure eviction itself becomes
class-aware (worst class first, youngest within it). Together these
protect the high class's p99 TTFT under overload while the low class
absorbs the sheds.

Elastic serving: `drain()` stops at a step boundary — quiesces the
device lanes through the `serve/decode.py` drain seam, requeues all
in-flight work (replayable from seeds), and returns a JSON-able state
snapshot (queue contents + per-request emitted-token counts + the
checkpoint timestamp). `serve/elastic.py` persists that snapshot into
the incarnation-scoped store with the PR 1 CRC conventions and
restores it into a fresh engine on the re-formed gang — possibly at a
different world size / TP degree, since replay-from-seed carries no
device state. The restored engine reports a first-class RECOVERY
metric (drain → first post-restore token) on `/serve`.

Single-owner design: one thread calls `submit()`/`step()`/
`run()`; `ServeMetrics` is internally locked so the debug HTTP frontend
can snapshot concurrently.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import jax.profiler
import numpy as np

from .. import faults
from ..numerics import numerics_contract
from ..ops.paged_attention import shared_decode_keys
from ..types import DistError
from .bucketing import bucket_for, bucket_lengths
from .cache import PagedKVCache
from .decode import (
    kernel_layers, layer_paths, masks_padding, paged_programs,
    step_shares_blocks, sync_slot_lanes,
)
from .kinds import kinds_of
from .metrics import ServeMetrics
from .queue import (
    DEFAULT_CLASS,
    ClassSpec,
    Completion,
    QueueFullError,
    Request,
    RequestQueue,
)

__all__ = ["Handoff", "PHASES", "ServeEngine", "StepRecord"]

# Faults the engine absorbs by requeueing work (the retry layer's
# transient taxonomy): injected connection resets and dropped requests.
# DistError "error" faults and real programming errors propagate.
_TRANSIENT = (ConnectionResetError, faults.FaultTimeout)


@dataclass
class _Prefill:
    """A slot mid-prefill: `pos` is the next prompt position to chunk
    (nonzero when a prefix-cache attach covered the prompt head); the
    request is not decoding (its lane stays parked) until the last
    chunk lands and `attach` seeds its state lanes. `seen` is the prefix
    index's insert count when the request last looked its prompt up."""

    req: Request
    pos: int = 0
    seen: int = 0


@dataclass
class _InFlight:
    """A device result the host has not read yet: `value` is a decode
    step's readback (the next token of every lane, then a sparse
    model's counters) or, with `first`, the one token a finished
    prefill sampled. `rows` ties it to the requests it was dispatched
    for: (slot, the token list of the request that held the slot then).
    A slot's token list is replaced whenever the slot changes hands, so
    a row whose list is no longer the slot's was evicted in between and
    its token is dropped."""

    value: object
    rows: List[Tuple[int, List[int]]]
    first: bool = False


# the host phases of a call, in the order a call runs them; each is a
# `serve:<phase>` span in a profiler trace and a key of `StepRecord.host_s`
PHASES = ("admit", "gauges", "prefill_tick", "decode_tick", "wait", "book")


@dataclass
class StepRecord:
    """What one `ServeEngine.step` call did (or one `flush()` outside a
    call), filled as the call goes and public as `engine.last_step` when
    it returns. Counts are of THIS call: what it dispatched (`chunks`,
    `decode_keys`) is read back by the next call, and what it read back
    (`resolved`, `tokens_booked`, `retired`, `moe`) was dispatched by the
    call before. No device value is kept: logits stay the caller's to ask
    for."""

    call: int  # index over the engine's life, from 1
    t0: Optional[float] = None  # the engine's clock when the call began
    queue_depth: int = 0  # after the call's first admission round
    admitted: int = 0
    prompt_tokens_admitted: int = 0
    prefix_tokens_attached: int = 0  # of them, matched in the prefix cache
    # (slot, start, tokens, bucket) of every prefill chunk, in dispatch order
    chunks: Tuple[Tuple[int, int, int, int], ...] = ()
    # one entry a decoding row, in slot order: the keys it attends (cached
    # and the one this step writes); empty when no decode step was dispatched
    decode_keys: Tuple[int, ...] = ()
    # of sum(decode_keys), the keys the step's attention kernel reads from
    # a copy another row uses too (`ops.paged_attention.shared_runs`:
    # whole compute blocks that several rows' tables hold); 0 without
    # `prefix_cache`, where no two rows hold one block
    decode_shared_keys: int = 0
    resolved: int = 0  # device results read back
    tokens_booked: int = 0  # tokens appended to the request they were for
    retired: int = 0
    # requests that lost their slot in this call and went back to the queue
    # (pool pressure, a better class, a transient fault)
    preempted: int = 0
    flush: Optional[str] = None  # why everything was read back, if it was
    # a sparse model's counters of the decode step this call read back:
    # rows, assignments, routed, experts_hit (a list, one a sparse layer)
    moe: Optional[Dict] = None
    # host seconds by the engine's clock: each phase, and the whole call
    host_s: Dict[str, float] = field(
        default_factory=lambda: dict.fromkeys(PHASES, 0.0)
    )
    step_s: float = 0.0

    @property
    def decode_rows(self) -> int:
        return len(self.decode_keys)


@dataclass
class Handoff:
    """A finished prefill FROZEN for migration (``role="prefill"``
    engines, `serve/disagg/`): the slot keeps its blocks and request
    binding — nothing decodes, nothing frees — until the migration
    plane exports the KV payload and `release_handoff` returns the slot
    to the pool. `first` is the token the prefill engine already
    sampled (its one key-split off `req.seed`), so the decode pool
    starts FROM the migrated first token with the carry key
    reconstructed purely from the seed (`serve/decode.py::carry_key`)
    — no device RNG state crosses the wire. It is None until the engine
    has read the token back, which `pop_handoffs` sees to."""

    req: Request
    slot: int
    length: int
    first: Optional[int] = None


class ServeEngine:
    def __init__(
        self,
        model,
        params,
        slots: int = 8,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        eos_id: Optional[int] = None,
        min_bucket: int = 16,
        clock=time.monotonic,
        metrics: Optional[ServeMetrics] = None,
        block_size: int = 16,
        pool_blocks: Optional[int] = None,
        prefill_chunk_tokens: Optional[int] = None,
        max_queue_depth: Optional[int] = None,
        mesh=None,
        tp_axis: str = "tp",
        kv_quant: bool = False,
        conservative_admission: bool = False,
        classes: Optional[Dict[str, ClassSpec]] = None,
        class_preemption: bool = True,
        prefix_cache: bool = False,
        precompiled=None,
        role: str = "both",
    ):
        # disaggregated serving (serve/disagg/): "prefill" freezes
        # finished prefills as Handoffs for the migration plane instead
        # of decoding them; "decode" admits work only via
        # attach_migrated (its queue holds preempted migrants awaiting
        # router pickup); "both" is the colocated PR 6 engine,
        # bit-for-bit.
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill', or 'decode', got {role!r}"
            )
        self.role = role
        self._handoff: List[Handoff] = []
        self.model = model
        self.params = params["params"] if "params" in params else params
        self.cfg = model.cfg
        # what is not carried with layers of some kind: the kind's record
        # says which features and why (`serve/kinds.py::Kind.not_carried`)
        asked = {
            "prefix_cache": ("prefix_cache=True", prefix_cache),
            "kv_quant": ("kv_quant=True", kv_quant),
            "mesh": ("mesh=", mesh is not None),
            "role": (f"role={role!r}", role != "both"),
            "precompiled": ("precompiled=", bool(precompiled)),
        }
        for kind in kinds_of(self.cfg):
            for feature, why in kind.not_carried.items():
                what, on = asked[feature]
                if on:
                    raise ValueError(
                        f"a model with {kind.name} layers cannot be served "
                        f"with {what} ({why})"
                    )
        # sparse (dropless MoE) layers: padding routes nowhere, and the
        # decode step's readback carries two counters a layer
        self._sparse_layers = len(getattr(self.cfg, "sparse_layers", ()))
        # sparse layers and layers that keep a state block are told which
        # positions of a chunk are padding (`serve/decode.py::paged_programs`)
        self._pad_id = -1 if masks_padding(self.cfg) else 0
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.clock = clock
        self.cache = PagedKVCache(
            model, slots, num_blocks=pool_blocks, block_size=block_size,
            quantized=kv_quant, chunk_tokens=prefill_chunk_tokens,
        )
        # prefix sharing: radix index over the refcounted pool — OPT-IN
        # (off keeps PR 6 pool semantics and accounting bit-for-bit)
        if prefix_cache:
            from .prefix import PrefixIndex

            self.prefix = PrefixIndex(self.cache)
        else:
            self.prefix = None
        # multi-tenant classes: weighted admission + class-ordered shed
        # in the queue; cross-class preemption here. None = the single
        # default class (PR 4 FIFO semantics, bit-for-bit).
        self.classes = dict(classes) if classes else None
        self.class_preemption = bool(classes) and class_preemption
        self.queue = RequestQueue(
            max_depth=max_queue_depth, classes=self.classes
        )
        self.metrics = metrics or ServeMetrics(
            clock=clock, slots=slots, classes=self.classes
        )
        self.metrics.slots = slots
        # displaced-by-class sheds (queued low-class work evicted by a
        # higher-class put) — exposed so drivers can account for
        # requests that will never complete. BOUNDED: only the newest
        # _max_shed_kept victims are kept (a long-lived engine under
        # sustained overload must not accumulate prompt arrays forever;
        # totals live in the per-class shed metrics).
        self.shed_requests: Dict[str, Request] = {}
        self._max_shed_kept = 1024
        # elastic restore bookkeeping: set by serve/elastic.py's
        # restore_into; the first post-restore emitted token closes the
        # recovery window (drain timestamp -> first token served)
        self._recovery_anchor: Optional[float] = None
        self._recovery_meta: tuple = (0, 0, -1)
        self.buckets = bucket_lengths(self.cfg.max_seq_len, min_bucket)
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1, got "
                f"{prefill_chunk_tokens}"
            )
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # conservative admission: reserve every request's WORST-CASE
        # block footprint at admission, so admitted work can always grow
        # to completion and pool-pressure preemption never fires —
        # trades pool utilization for churn-free scheduling (and makes
        # "concurrently admitted requests" a direct measure of pool
        # capacity). `_reserved` tracks
        # the active set's worst-case total.
        self.conservative_admission = conservative_admission
        self._reserved = 0
        self.mesh = mesh
        jmesh = getattr(mesh, "jax_mesh", mesh)
        (
            self._prefill_chunk,
            self._first_token,
            self._attach,
            self._step,
        ) = paged_programs(model, temperature, top_k, jmesh, tp_axis)
        # which path each kind of layer takes (`serve/decode.py::
        # layer_paths`), for the metrics: in the step, and in a chunk of
        # each length the engine dispatches — facts of the engine's
        # lifetime
        layers = model.cfg.n_layers
        step_paths = layer_paths(self.cache, slots, 1, jmesh, tp_axis)
        chunk_paths = {
            C: layer_paths(self.cache, 1, C, jmesh, tp_axis)
            for C in {*self.buckets, prefill_chunk_tokens} - {None}
        }
        self._decode_kernel = kernel_layers(step_paths) == layers
        # only an attached prefix puts one block in two rows' tables
        self._decode_shares = self.prefix is not None and step_shares_blocks(
            self.cache, step_paths, jmesh, tp_axis
        )
        self._chunk_kernel_layers = {
            C: kernel_layers(paths) for C, paths in chunk_paths.items()
        }
        self.metrics.record_layer_paths(
            step_paths,
            chunk_paths[prefill_chunk_tokens or max(chunk_paths)],
        )
        if precompiled:
            # resize fast path (serve/prewarm.py): overlay pre-warmed
            # executables — matching shapes skip trace AND compile,
            # everything else falls through to the jit quadruple
            from .prewarm import attach_precompiled

            (
                self._prefill_chunk,
                self._first_token,
                self._attach,
                self._step,
            ) = attach_precompiled(
                (
                    self._prefill_chunk,
                    self._first_token,
                    self._attach,
                    self._step,
                ),
                precompiled,
                slots,
            )
        S = slots
        self._slot_req: List[Optional[Request]] = [None] * S
        self._slot_tokens: List[List[int]] = [[] for _ in range(S)]
        self._prefilling: Dict[int, _Prefill] = {}
        self._decoding: set = set()
        # results queued on the device and not read back, oldest first:
        # a `step()` call reads what earlier calls left here only after
        # it has dispatched its own programs
        self._inflight: Deque[_InFlight] = deque()
        # device-resident per-slot state, donated through every step —
        # the per-token hot path touches the host only for the (S,)
        # next-token readback, one call late; block tables stay
        # host-side numpy and ride into each program call as copies
        # (see serve/decode.py)
        import jax.numpy as jnp

        self._dev_lengths = jnp.zeros((S,), jnp.int32)
        self._dev_tokens = jnp.zeros((S,), jnp.int32)
        self._dev_rngs = jnp.zeros((S, 2), jnp.uint32)
        if mesh is not None:
            from ..models.transformer import sharding_rules
            from ..parallel.sharding import shard_params
            from ..parallel.tensor_parallel import (
                replicate_tree,
                shard_kv_pool,
            )

            self.params, _ = shard_params(
                self.params, mesh,
                sharding_rules(tp_axis=tp_axis, fsdp_axis=None),
            )
            self.cache.tree = shard_kv_pool(
                self.cache.tree, mesh, axis=tp_axis
            )
            (
                self._dev_lengths,
                self._dev_tokens,
                self._dev_rngs,
            ) = replicate_tree(
                (self._dev_lengths, self._dev_tokens, self._dev_rngs), mesh
            )
        self.completions: Dict[str, Completion] = {}
        # what the last call did (`StepRecord`). `_rec` is the record of
        # the call that is running or, between calls (`t0` None), of the
        # next one: what happens to a request between two calls (a seam's
        # eviction, a migrated landing) is booked to the call that follows
        self.last_step: Optional[StepRecord] = None
        self._rec = StepRecord(call=1)

    # -- the call's record -------------------------------------------------
    def _open(self) -> StepRecord:
        self._rec.t0 = self.clock()
        return self._rec

    def _close(self, rec: StepRecord) -> None:
        """End the call's record: its whole time, the metrics' one
        `record_host`, and the zero-length `serve:step_done` that carries
        its counts onto the host line of a profiler trace."""
        rec.step_s = self.clock() - rec.t0
        self.metrics.record_host(rec)
        with jax.profiler.TraceAnnotation(
            "serve:step_done", call=rec.call, queue=rec.queue_depth,
            admitted=rec.admitted, prompt=rec.prompt_tokens_admitted,
            attached=rec.prefix_tokens_attached, chunks=len(rec.chunks),
            chunk_tokens=sum(c[2] for c in rec.chunks),
            rows=rec.decode_rows, keys=sum(rec.decode_keys),
            resolved=rec.resolved, booked=rec.tokens_booked,
            retired=rec.retired, preempted=rec.preempted,
        ):
            pass
        self.last_step, self._rec = rec, StepRecord(call=rec.call + 1)

    @contextlib.contextmanager
    def _phase(self, name: str, **args):
        """One host phase of the running call: a `serve:<name>` span on
        the profiler's clock and its seconds, by the engine's clock, added
        to the record's."""
        t = self.clock()
        try:
            with jax.profiler.TraceAnnotation("serve:" + name, **args):
                yield
        finally:
            self._rec.host_s[name] += self.clock() - t

    # -- admission ---------------------------------------------------------
    def submit(
        self,
        prompt,
        max_new_tokens: int,
        rid: Optional[str] = None,
        seed: int = 0,
        arrival_time: Optional[float] = None,
        tenant: str = "",
        klass: str = DEFAULT_CLASS,
    ) -> str:
        """Enqueue one generation request; returns its request id.
        Raises `QueueFullError` (counted in metrics as a shed) when
        bounded admission is on and the request's class is the worst
        present; a HIGHER-class submit into a full queue instead
        displaces the newest worst-class queued request (recorded in
        `shed_requests` + per-class metrics) and succeeds.

        `arrival_time` (engine-clock seconds) is trace-replay support:
        a single-threaded replay driver can only call submit() between
        steps, so stamping the clock would erase the queueing delay a
        request already served before the driver got to it — pass the
        TRUE front-door arrival and TTFT/e2e account for it."""
        req = Request(
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=max_new_tokens,
            rid=rid or "",
            seed=seed,
            tenant=tenant,
            klass=klass,
        )
        L = len(req.prompt)
        if L < 1:
            raise ValueError("empty prompt")
        if L + max_new_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt ({L}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_seq_len ({self.cfg.max_seq_len})"
            )
        bucket_for(L, self.buckets)  # raises when no bucket fits
        worst = self.cache.blocks_for(L + max_new_tokens)
        if worst > self.cache.num_blocks:
            raise ValueError(
                f"request needs up to {worst} blocks but the pool has "
                f"{self.cache.num_blocks} (grow pool_blocks or shrink "
                f"the request)"
            )
        req.arrival_time = (
            self.clock() if arrival_time is None else arrival_time
        )
        try:
            victim = self.queue.put(req)
        except QueueFullError:
            self.metrics.record_shed(req.klass)
            raise
        if victim is not None:
            # class-ordered overload shed: a queued worse-class request
            # made room for this one (it never ran; callers see it in
            # shed_requests, metrics count it against ITS class)
            self.shed_requests[victim.rid] = victim
            while len(self.shed_requests) > self._max_shed_kept:
                self.shed_requests.pop(next(iter(self.shed_requests)))
            self.metrics.record_shed(victim.klass)
        self.metrics.record_submit(req.arrival_time, req.klass)
        return req.rid

    def _chunk_len(self, L: int) -> int:
        """Upper bound on the first prefill program length for a prompt
        of length L: the per-step token budget when chunking is on,
        else the prompt's bucket (unchunked, per-bucket programs
        exactly like PR 4). The admission gate sizes its first-chunk
        block estimate from this."""
        if self.prefill_chunk_tokens is not None:
            return self.prefill_chunk_tokens
        return bucket_for(L, self.buckets)

    def _admit(self) -> int:
        if self.role == "decode":
            # decode-pool engines admit ONLY via attach_migrated;
            # anything queued here is a preempted migrant waiting for
            # the disagg router to route it back through a prefill
            # engine (replay-from-seed)
            return 0
        return self._admit_queue()

    def _admit_queue(self) -> int:
        """Backfill free slots from the queue (continuous batching:
        called at the top of every step, so retirement and admission
        interleave mid-stream). The queue's weighted round-robin picks
        the candidate; when that candidate cannot acquire resources,
        strictly-HIGHER-priority class heads also get a try (they may
        preempt a worse class's in-flight work — `_class_preempt_for`),
        so overload never wedges the high class behind a low-class head
        that cannot make progress. Admission stops when no candidate
        can acquire a slot + first-chunk blocks — the allocate-on-write
        backpressure gate. Returns the number admitted this round."""
        admitted = 0
        while True:
            candidates = self._admission_candidates()
            if not candidates:
                return admitted
            progressed = False
            for head in candidates:
                outcome = self._try_admit(head)
                if outcome == "admitted":
                    admitted += 1
                    progressed = True
                    break
                if outcome == "stop":
                    return admitted
                # "blocked": this candidate cannot acquire resources —
                # a better class may still preempt its way in
            if not progressed:
                return admitted

    def _admission_candidates(self) -> List[Request]:
        """The SWRR-selected head first, then heads of STRICTLY better
        priority classes, best-first (single-class queues: just the
        head). Worse classes never bypass a blocked candidate — they
        could only squeeze into space the blocked class will preempt
        right back, churning admissions without progress."""
        heads = self.queue.class_heads()
        if not heads:
            return []
        sel = self.queue.peek()
        if sel is None or not self.classes:
            return [sel] if sel is not None else []
        sp = self.classes[sel.klass].priority
        rest = sorted(
            (
                r
                for r in heads.values()
                if r is not sel and self.classes[r.klass].priority < sp
            ),
            key=lambda r: self.classes[r.klass].priority,
        )
        return [sel] + rest

    def _try_admit(self, head: Request) -> str:
        """Acquire slot + first-chunk blocks for `head` (class-preempting
        worse in-flight work while allowed) and admit it. Returns
        "admitted", "blocked" (resources unavailable for THIS candidate),
        or "stop" (end the whole admission round).

        ALL gates precheck — before anyone is evicted — that evicting
        the available worse-class victims could satisfy them JOINTLY
        (eviction frees a victim's slot, blocks, and reservation at
        once, so each gate's feasibility at the evict-everything bound
        is monotone and the per-gate prechecks compose). A candidate
        that would stay blocked after evicting every victim must not
        evict at all — otherwise each admission round would pointlessly
        kill worse-class work (possibly work admitted moments earlier),
        churning requeues without any gold progress."""
        head_len = len(head.prompt)
        # first-chunk sizing ignores a possible prefix-cache hit (the
        # match runs after the fire points, post-acquisition): a hit
        # only ever needs FEWER fresh blocks, so the gate errs toward
        # backpressure, never toward overcommit
        need = self.cache.blocks_for(min(self._chunk_len(head_len), head_len))
        victims = self._class_victims(head)
        if need > self.cache.free_blocks + sum(
            # only a victim's EXCLUSIVE blocks are guaranteed back —
            # shared prefix blocks outlive the eviction
            self.cache.exclusive_blocks(s) for s in victims
        ):
            return "blocked"  # pool backpressure: wait for retires
        if self.conservative_admission:
            worst = self.cache.blocks_for(head_len + head.max_new_tokens)
            releasable = sum(
                self._worst_blocks(self._slot_req[s]) for s in victims
            )
            if self._reserved - releasable + worst > self.cache.num_blocks:
                return "blocked"  # worst-case reservation gate
        if (
            len(self.cache.active_slots) >= self.cache.slots
            and not victims
        ):
            return "blocked"  # slot pressure with nothing evictable
        # feasible: now acquire, evicting as needed
        while need > self.cache.free_blocks:
            if not self._class_preempt_for(head):
                return "blocked"
        if self.conservative_admission:
            while self._reserved + worst > self.cache.num_blocks:
                if not self._class_preempt_for(head):
                    return "blocked"
        slot = self.cache.allocate()
        while slot is None:
            if not self._class_preempt_for(head):
                return "blocked"
            slot = self.cache.allocate()
        if not self.queue.pop_specific(head):
            # racing submitter drained it between checks
            self.cache.free(slot)
            return "stop"
        req = head
        try:
            faults.fire("serve.admit", rid=req.rid)
        except _TRANSIENT:
            # transient admission fault: the request goes back to the
            # HEAD (arrival order preserved) and this round stops —
            # the next step() retries
            self.cache.free(slot)
            req.requeues += 1
            self.queue.requeue_front(req)
            self.metrics.record_requeue()
            return "stop"
        pos0 = 0
        if self.prefix is not None:
            try:
                faults.fire("serve.prefix_attach", rid=req.rid)
            except _TRANSIENT:
                # transient attach fault: nothing was attached yet (the
                # slot holds zero blocks), so freeing it is clean; the
                # replay re-matches the index and attaches the SAME
                # shared blocks deterministically
                self.cache.free(slot)
                req.requeues += 1
                self.queue.requeue_front(req)
                self.metrics.record_requeue()
                return "stop"
            # hit/miss/reuse accounting lives in the INDEX (the next
            # record_pool snapshots its stats() into the metrics)
            blocks, matched = self.prefix.match(
                self._prefix_scope(req), req.prompt.tolist()
            )
            if matched > 0:
                self.cache.attach_prefix(slot, blocks)
                pos0 = matched
        self._slot_req[slot] = req
        self._slot_tokens[slot] = []
        self._prefilling[slot] = _Prefill(
            req, pos=pos0, seen=self.prefix.inserts if self.prefix else 0
        )
        self._reserved += self._worst_blocks(req)
        self.metrics.record_admit()
        # the request changes hands: queue -> slot. A replay's stamps are
        # its own: every admission starts them again
        req.admit_time, req.token_times = self.clock(), []
        rec = self._rec
        rec.admitted += 1
        rec.prompt_tokens_admitted += head_len
        rec.prefix_tokens_attached += pos0
        with jax.profiler.TraceAnnotation(
            "serve:admitted", prompt=head_len, attached=pos0,
            queue_us=int(1e6 * (req.admit_time - req.arrival_time)),
        ):
            pass
        return "admitted"

    def _prefix_scope(self, req: Request):
        """The sharing boundary for `req`'s prefix-cache entries —
        `serve.prefix.prefix_scope`, the one definition shared with the
        DP router's session affinity (ISSUE 15)."""
        from .prefix import prefix_scope

        return prefix_scope(self.classes, req.klass, req.tenant)

    def _class_victims(self, head: Request) -> List[int]:
        """Slots holding in-flight work of a class STRICTLY below
        `head`'s priority — what cross-class preemption may evict
        (equal-or-better classes never; same-class pressure stays
        ordinary backpressure)."""
        if not self.class_preemption:
            return []
        hp = self.classes[head.klass].priority
        return [
            s
            for s in range(self.cache.slots)
            if self._slot_req[s] is not None
            and self.classes[self._slot_req[s].klass].priority > hp
        ]

    def _class_preempt_for(self, head: Request) -> bool:
        """Cross-class preemption: evict the youngest in-flight request
        of the WORST class strictly below `head`'s priority; the evictee
        requeues at its class head and replays token-identically from
        its seed. False when no victim exists."""
        victims = self._class_victims(head)
        if not victims:
            return False
        victim = max(
            victims,
            key=lambda s: (
                self.classes[self._slot_req[s].klass].priority,
                self._slot_req[s].arrival_time,
            ),
        )
        klass = self._slot_req[victim].klass
        self._evict(victim, requeue_counter=False)
        self.metrics.record_class_preempt(klass)
        return True

    def _worst_blocks(self, req: Request) -> int:
        """A request's worst-case block footprint (prompt + full token
        budget) — the conservative-admission reservation unit."""
        return self.cache.blocks_for(len(req.prompt) + req.max_new_tokens)

    # -- chunked prefill ---------------------------------------------------
    def _prefill_tick(self) -> None:
        """Advance prefills. Unchunked: run EVERY pending prefill to
        completion (one bucketed program each — PR 4 admission
        semantics). Chunked: spend a per-step TOKEN BUDGET of
        `prefill_chunk_tokens` program tokens, shortest-remaining-
        prefill first — short prompts SHARE one step's budget (a
        32-token budget prefills two 16-token prompts in the same step)
        while a long prompt advances one budget-sized chunk per step,
        interleaved with decode. A short arrival therefore never waits
        behind a whole long prefill (the bounded-TTFT policy), and the
        prefill service rate is budget/step rather than one program per
        step. At least one program runs per tick, so a budget below the
        smallest bucket still makes progress."""
        import jax.numpy as jnp
        budget = self.prefill_chunk_tokens
        spent = 0
        while self._prefilling:
            # class priority outranks shortest-remaining: a gold prompt's
            # chunks never queue behind bronze prefill work (single-class
            # engines: pure shortest-remaining-first, the PR 6 policy)
            slot = min(
                self._prefilling,
                key=lambda s: (
                    self.classes[self._prefilling[s].req.klass].priority
                    if self.classes
                    else 0,
                    len(self._prefilling[s].req.prompt)
                    - self._prefilling[s].pos,
                    self._prefilling[s].req.arrival_time,
                ),
            )
            pf = self._prefilling[slot]
            req = pf.req
            L = len(req.prompt)
            if pf.pos == 0 and self.prefix and pf.seen != self.prefix.inserts:
                self._attach_late(slot, pf)
            if budget is None:
                # bucket over the REMAINING prompt: a prefix-cache
                # attach starts the (single, unchunked) program at the
                # first uncached position, not at 0
                C = bucket_for(L - pf.pos, self.buckets)
            else:
                # program length this tick: the bucket covering what the
                # remaining budget can spend, capped at the budget (so
                # the compiled chunk shapes stay a bounded set: buckets
                # <= budget, plus the budget itself)
                want = max(1, min(L - pf.pos, budget - spent))
                C = min(bucket_for(want, self.buckets), budget)
                if spent and spent + C > budget:
                    return  # budget spent: yield to decode
            end = min(pf.pos + C, L)
            if not self._ensure_or_preempt(slot, end - 1, pf.pos):
                continue  # the prefilling request itself got evicted
            if not self._cow_or_preempt(slot, pf.pos):
                continue  # ditto, while claiming a copy-on-write block
            try:
                faults.fire("serve.prefill_chunk", rid=req.rid, pos=pf.pos)
            except _TRANSIENT:
                self._evict(slot, requeue_counter=True)
                continue
            # padding is token 0, or -1 where a layer must
            # tell it from a token (`serve/decode.py::paged_programs`)
            chunk = np.full((1, C), self._pad_id, np.int32)
            chunk[0, : end - pf.pos] = req.prompt[pf.pos:end]
            # what the chunk is: into the call's record, and from there
            # onto the host line of a profiler trace
            start, tokens = pf.pos, end - pf.pos
            self._rec.chunks += ((slot, start, tokens, C),)
            with jax.profiler.TraceAnnotation(
                "serve:prefill_chunk", slot=slot, start=start,
                tokens=tokens, bucket=C,
            ):
                self.cache.tree, logits = self._prefill_chunk(
                    self.params,
                    self.cache.tree,
                    jnp.asarray(chunk),
                    self.cache.tables(slice(slot, slot + 1)),
                    pf.pos,
                )
            self.metrics.record_prefill_chunk(
                self._chunk_kernel_layers[C], self.model.cfg.n_layers
            )
            pf.pos = end
            spent += C
            if end < L:
                if budget is not None and spent >= budget:
                    return  # budget spent: yield to decode
                continue
            # final chunk: sample the first token at the TRUE prompt end
            # and fuse the request's lanes into the donated slot vectors.
            # The token stays on the device (`attach` takes it from
            # there); the host reads it with the next call's resolve
            first_dev, key = self._first_token(
                logits, (L - 1) - start, req.seed
            )
            (
                self._dev_lengths,
                self._dev_tokens,
                self._dev_rngs,
            ) = self._attach(
                self._dev_lengths,
                self._dev_tokens,
                self._dev_rngs,
                slot,
                L,
                first_dev,
                key,
            )
            self.cache.lengths[slot] = L  # host mirror for introspection
            if self.prefix is not None:
                # index the prompt's blocks NOW, before the first decode
                # write lands — entries hold PROMPT K/V only, so decoded
                # tokens can never be served to another request (the
                # slot's own next write into its partial tail block
                # copy-on-writes it, leaving the indexed original
                # pristine)
                self.prefix.insert(
                    self._prefix_scope(req), req.prompt.tolist(),
                    self.cache.slot_blocks(slot),
                )
            del self._prefilling[slot]
            self._await(first_dev, [slot], first=True)
            if req.max_new_tokens == 1:
                # nothing left to decode, whatever the role (migrating
                # would move blocks only to free them): the slot waits,
                # parked, for `_resolve` to read the token and retire it
                pass
            elif self.role == "prefill":
                # freeze for migration: the slot keeps its request and
                # blocks (the migration plane exports them), the lane
                # stays parked. `_resolve` fills `first` and lands the
                # TTFT in this pool's window; completion (and TPOT)
                # will land in the decode pool's.
                self._handoff.append(Handoff(req=req, slot=slot, length=L))
            else:
                self._decoding.add(slot)
            if budget is not None and spent >= budget:
                return  # budget spent: yield to decode

    def _attach_late(self, slot: int, pf: _Prefill) -> None:
        """A request that found nothing in the prefix index at admission
        looks again before its first chunk, if prompts were indexed since:
        requests that arrive while ANOTHER request is still prefilling their
        shared head all miss at admission (a cold index and a burst of
        arrivals: every one of them would compute the whole head), and
        shortest-remaining-first keeps them at position 0 until that request
        has indexed it. The slot holds no block yet, so the attach is
        admission's."""
        pf.seen = self.prefix.inserts
        if self.cache.slot_blocks(slot):
            return
        blocks, matched = self.prefix.match(
            self._prefix_scope(pf.req), pf.req.prompt.tolist(), again=True
        )
        if not matched:
            return
        self.cache.attach_prefix(slot, blocks)
        pf.pos = matched
        self._rec.prefix_tokens_attached += matched
        with jax.profiler.TraceAnnotation(
            "serve:attached_late", slot=slot, attached=matched
        ):
            pass

    # -- pool pressure -----------------------------------------------------
    def _preempt_for_pool(self, slot: int) -> bool:
        """ONE pool-pressure eviction: the WORST-CLASS then youngest
        active request loses its slot and blocks (single-class engines:
        plain youngest-first, the PR 6 policy). Returns False when the
        victim was `slot` itself — the caller's own request got evicted
        and its retry loop must stop. The ONE copy of the pressure
        policy: block growth and copy-on-write both retry through it,
        so they can never diverge."""
        victims = [
            s
            for s in range(self.cache.slots)
            if self._slot_req[s] is not None
        ]
        victim = max(
            victims,
            key=lambda s: (
                self.classes[self._slot_req[s].klass].priority
                if self.classes
                else 0,
                self._slot_req[s].arrival_time,
            ),
        )
        klass = self._slot_req[victim].klass
        self._evict(victim, requeue_counter=False)
        self.metrics.record_preempt(klass=klass)
        return victim != slot

    def _ensure_or_preempt(
        self, slot: int, upto_pos: int, first_pos: int
    ) -> bool:
        """Grow `slot`'s block table to cover `upto_pos`, evicting via
        `_preempt_for_pool` while the pool is dry. Returns False when
        the grower itself got evicted. Deadlock-free: submit()
        guarantees any single request's worst case fits the pool, so
        the oldest request of the best class always wins. `first_pos`
        is where the write being prepared starts: a window layer's
        blocks behind its window are recycled (`serve/cache.py`)."""
        while not self.cache.ensure_blocks(slot, upto_pos, first_pos):
            if not self._preempt_for_pool(slot):
                return False
        return True

    def _cow_or_preempt(self, slot: int, pos: int) -> bool:
        """Copy-on-write the block a write at `pos` would land in while
        it is shared or index-pinned, evicting via `_preempt_for_pool`
        while the pool cannot spare the copy's block. Returns False
        when the writer itself got evicted. Almost always a no-op: only
        the FIRST write past a shared partial boundary (or into the
        slot's own freshly indexed tail) copies; the copy is private
        from then on."""
        while not self.cache.cow_block(slot, pos):
            if not self._preempt_for_pool(slot):
                return False
        return True

    def _evict(self, slot: int, requeue_counter: bool) -> None:
        """Push a slot's request back to the queue HEAD and free the
        slot + its blocks (preemption and transient-chunk-fault path).
        The replay is token-identical — per-request seeds."""
        req = self._slot_req[slot]
        req.requeues += 1
        req.first_token_time = None
        self._rec.preempted += 1
        self._slot_req[slot] = None
        self._slot_tokens[slot] = []
        self._prefilling.pop(slot, None)
        self._decoding.discard(slot)
        # an evicted FROZEN handoff replays through prefill again —
        # its record must go, or the migration plane would export a
        # freed (possibly reallocated) slot's blocks
        self._handoff = [h for h in self._handoff if h.slot != slot]
        self.queue.requeue_front(req)
        self.cache.free(slot)
        self._reserved -= self._worst_blocks(req)
        if requeue_counter:
            self.metrics.record_requeue()

    # -- decode ------------------------------------------------------------
    @numerics_contract(
        "token_exact",
        note="a greedy request's emitted token stream is identical "
        "across resizes, restores, and cache-sharing on/off (PR 16; "
        "per-request seeds + fold_in discipline make replay exact)",
    )
    def step(self) -> bool:
        """One engine iteration, with one call's device work in flight.

        The call admits and schedules from what the host already knows,
        dispatches its programs — prefill chunks (one budget's worth
        when chunking is on), then one decode step over every decoding
        slot — and only THEN reads back what the PREVIOUS call
        dispatched: the first token of each prefill that call finished,
        then its decode step's tokens. It appends them, stamps TTFT,
        and retires what an EOS or its budget ends. So a call returns
        the previous call's tokens, the device always has the next
        programs queued while the host works, and no host read of a
        device value stands between two dispatches of one call.

        What follows from the lag: a row that spends its budget leaves
        the decoding set at that dispatch (a count: tokens emitted plus
        tokens in flight) and keeps its slot, parked, until the next
        call reads its last token. A row whose EOS is still in flight
        decodes one more lane; that token is dropped, and its write
        lands in a block the row still owns. A result is appended only
        to the request it was dispatched for (`_InFlight`): eviction in
        between drops it, and the replay is token-identical.

        `snapshot_state`, `drain`, `requeue_inflight`, `pop_handoffs`,
        `release_handoff` and `attach_migrated` read back everything
        outstanding first (`flush`); so does a call with nothing to
        dispatch, which is how `run()` ends. Nothing else does: whoever
        needs the host's state between calls, or whole steps inside a
        profiler trace (`stop_trace` cuts what is in flight), calls
        `flush()` itself. `ServeMetrics` counts both sides under
        `pipeline`: `overlap_share` (decode steps dispatched over an
        outstanding result, of all decode steps) and `flushes` by cause.

        What the call did is `engine.last_step` when it returns (a
        `StepRecord`; the module docstring says what is in it and which
        spans a profiler trace keeps of it).

        Returns True while work remains: a result outstanding, active
        slots, prefills, or queued requests."""
        outstanding, faulted = len(self._inflight), False
        rec = self._open()
        with jax.profiler.TraceAnnotation("serve:step"):
            try:
                with self._phase("admit"):
                    self._admit()
                with self._phase("gauges"):
                    self._gauges(rec)
                while True:
                    with self._phase("prefill_tick"):
                        self._prefill_tick()
                    # an eviction under pool pressure frees a slot MID-STEP;
                    # unchunked keeps PR 4's semantics by backfilling and
                    # prefilling it in the same iteration. Chunked mode still
                    # grants the slot (next step's tick prefills it) but spends
                    # no further chunk budget.
                    with self._phase("admit"):
                        backfilled = self._admit()
                    if backfilled == 0 or self.prefill_chunk_tokens is not None:
                        break
                if self._decoding:
                    try:
                        faults.fire("serve.step", n_active=len(self._decoding))
                    except _TRANSIENT:
                        self.requeue_inflight()
                        faulted = True
                    else:
                        with self._phase("decode_tick"):
                            self._decode_tick(overlapped=outstanding > 0)
                if not faulted:
                    if outstanding and not (rec.chunks or rec.decode_keys):
                        # nothing to run ahead of it
                        rec.flush = "idle"
                        self.metrics.record_flush("idle")
                    self._resolve(outstanding)
            finally:
                self._close(rec)
        return (
            faulted
            or bool(self._inflight)
            or bool(self._decoding)
            or bool(self._prefilling)
            or bool(self.queue)
        )

    def _gauges(self, rec: StepRecord) -> None:
        """The metrics' per-call gauges: queue and slots, then the pool."""
        rec.queue_depth = self.queue.depth
        self.metrics.record_step(
            rec.queue_depth,
            len(self.cache.active_slots),
            class_depths=(
                self.queue.class_depths() if self.classes else None
            ),
        )
        self.metrics.record_pool(
            self.cache.live_blocks,
            self.cache.num_blocks,
            self.cache.bytes_per_block,
            # requests that hold blocks and will use them here: frozen
            # handoffs are the migration plane's
            sum(r is not None for r in self._slot_req) - len(self._handoff),
            self.cache.dense_bytes_per_request,
            wire_dtype=self.cache.wire_dtype,
            scale_bytes_per_block=self.cache.scale_bytes_per_block,
            effective_slots=self.cache.effective_slots,
            shared_blocks=self.cache.shared_blocks,
            cached_free_blocks=self.cache.cached_free_blocks,
            cow_copies=self.cache.cow_copies,
            bytes_deduplicated=self.cache.bytes_deduplicated,
            prefix_stats=self.prefix.stats() if self.prefix else None,
            gauges=self.cache.pool_gauges(),
        )

    def _decode_tick(self, overlapped: bool) -> None:
        """Dispatch one decode step over every decoding slot; its tokens
        are read by a later `_resolve`."""
        # allocate-on-write: every decoding slot must own the block its
        # next token lands in BEFORE the batched write (preemption may
        # shrink the decoding set here)
        for s in sorted(self._decoding):
            if s not in self._decoding:  # evicted by an earlier growth
                continue
            at = int(self.cache.lengths[s])
            if not self._ensure_or_preempt(s, at, at):
                continue
            # first decode write past a shared/indexed prefix boundary
            # must own a private copy of that block (CoW)
            self._cow_or_preempt(s, at)
        active = sorted(self._decoding)
        if not active:
            return
        # every held slot that does not decode rides along PARKED, its
        # table row handed over all-invalid. A MID-PREFILL slot's row
        # already holds real blocks (chunks land as they arrive): the
        # parked lane's garbage write must drop instead of scattering
        # into the request's own block 0. FROZEN handoff slots are the
        # same hazard with higher stakes: their blocks are the migration
        # payload. A row that waits for its last token to be read costs
        # the attention kernel nothing this way. Retired rows are
        # already all-invalid via free().
        parked = [
            s
            for s, req in enumerate(self._slot_req)
            if req is not None and s not in self._decoding
        ]
        # the rows that decode and the keys they attend (each row's cached
        # keys and the one this step writes): into the call's record, and
        # from there onto the host line of a profiler trace
        rec = self._rec
        rec.decode_keys = tuple((self.cache.lengths[active] + 1).tolist())
        tables = self.cache.tables(parked=parked)
        if self._decode_shares:
            rec.decode_shared_keys = shared_decode_keys(
                tables, self.cache.lengths, self.cache.invalid_block,
                self.cache.block_size,
            )
        with jax.profiler.TraceAnnotation(
            "serve:decode_step", rows=rec.decode_rows,
            keys=sum(rec.decode_keys), shared=rec.decode_shared_keys,
        ):
            (
                self.cache.tree,
                self._dev_lengths,
                self._dev_tokens,
                self._dev_rngs,
                readback,
            ) = self._step(
                self.params,
                self.cache.tree,
                self._dev_lengths,
                self._dev_tokens,
                self._dev_rngs,
                tables,
            )
        self.metrics.record_decode_step(
            self._decode_kernel, overlapped, sum(rec.decode_keys),
            rec.decode_shared_keys,
        )
        self._await(readback, active)
        # the host mirror advances at dispatch: the next call grows
        # blocks and counts budgets from it before these tokens are read
        self.cache.lengths[active] += 1
        for s in active:
            req = self._slot_req[s]
            sent = self.cache.lengths[s] - len(req.prompt) + 1
            if sent >= req.max_new_tokens:
                self._decoding.discard(s)  # by count; retired at resolve

    def _await(self, value, slots, first: bool = False) -> None:
        """Queue a device result for a later `_resolve`, tied to the
        requests that hold `slots` now; its copy to the host starts as
        soon as the device has it."""
        value.copy_to_host_async()
        self._inflight.append(
            _InFlight(value, [(s, self._slot_tokens[s]) for s in slots], first)
        )

    def _resolve(self, n: int) -> None:
        """Read back the `n` oldest outstanding results, in dispatch
        order (a finished prefill's first token before the tokens of
        the decode step behind it: TTFT is stamped when the host holds
        the token, and it is ready a decode step sooner), and do the
        bookkeeping the host needs the tokens for."""
        for _ in range(n):
            res = self._inflight.popleft()
            with self._phase("wait", first=int(res.first)):
                host = np.asarray(res.value)  # blocks until the device is there
            with self._phase("book"):
                self._book(res, host)

    def _book(self, res: _InFlight, host) -> None:
        """One result's bookkeeping, once the host holds it: append and
        stamp each token, retire what it ends."""
        rec = self._rec
        rec.resolved += 1
        if self._sparse_layers and not res.first:
            self._record_moe_step(host[len(self._slot_req):], len(res.rows))
        now = self.clock()
        for slot, toks in res.rows:
            if self._slot_tokens[slot] is not toks:
                continue  # evicted since: not the next tenant's token
            req = self._slot_req[slot]
            tok = int(host) if res.first else int(host[slot])
            toks.append(tok)
            req.token_times.append(now)
            rec.tokens_booked += 1
            if res.first:
                req.first_token_time = now
                self._note_recovery(now)
            if self.eos_id is not None and tok == self.eos_id:
                self._retire(slot, now, "eos")
            elif len(toks) >= req.max_new_tokens:
                self._retire(slot, now, "length")
            elif res.first and self.role == "prefill":
                # TTFT is DONE — the first token exists — so it
                # lands in this pool's window now
                (handoff,) = (h for h in self._handoff if h.slot == slot)
                handoff.first = tok
                self.metrics.record_first_token(
                    now, now - req.arrival_time, klass=req.klass
                )

    def flush(self, cause: str = "caller") -> None:
        """Read back everything outstanding, so that the host's state
        (`completions`, token lists, `Handoff.first`) holds every token
        dispatched: the seams that read or hand out that state call this
        first, each under its own `cause` in `pipeline.flushes`. The
        device is then idle until the next `step()`. Outside a call it
        fills a `StepRecord` of its own (`flush` = the cause)."""
        if not self._inflight:
            return
        own = self._rec.t0 is None  # no call is running
        rec = self._open() if own else self._rec
        rec.flush = cause
        self.metrics.record_flush(cause)
        try:
            self._resolve(len(self._inflight))
        finally:
            if own:
                self._close(rec)

    def _record_moe_step(self, counters, rows: int) -> None:
        """One decode step's sparse-layer counters, (assignments, experts
        hit) a layer: into the call's record and the metrics, and from
        the record — for a reader that pairs them with the same steps'
        device time — onto the host line of a profiler trace. All three
        are written when the step is READ BACK, a call after its
        dispatch: a reader that wants one a step of its slice calls
        `flush()` before the trace starts and before it stops."""
        moe = self._rec.moe = {
            "rows": rows,
            "assignments": int(counters[0::2].sum()),
            # what the routers chose: `top_k` experts a live row and sparse
            # layer, of which this chip computed its held experts' share
            "routed": rows * self.cfg.sparse_top_k * self._sparse_layers,
            "experts_hit": counters[1::2].tolist(),  # per sparse layer
        }
        self.metrics.record_moe_step(
            moe["assignments"], moe["experts_hit"], moe["routed"]
        )
        with jax.profiler.TraceAnnotation(
            "serve:moe_step", rows=rows, assignments=moe["assignments"],
            routed=moe["routed"],
            **{f"hit{i}": h for i, h in enumerate(moe["experts_hit"])},
        ):
            pass

    def run(self, max_steps: Optional[int] = None) -> Dict[str, Completion]:
        """Drive step() until the queue and slots drain and the last
        result is read back (or max_steps); returns the completion map."""
        n = 0
        while self.step():
            n += 1
            if max_steps is not None and n >= max_steps:
                raise DistError(
                    f"serve engine did not drain within {max_steps} steps "
                    f"(active={len(self.cache.active_slots)}, "
                    f"queued={self.queue.depth})"
                )
        return self.completions

    # -- retirement / fault recovery ---------------------------------------
    def _retire(self, slot: int, now: float, reason: str) -> None:
        req = self._slot_req[slot]
        toks = self._slot_tokens[slot]
        n = len(toks)
        tpot = (
            (now - req.first_token_time) / (n - 1) if n > 1 else 0.0
        )
        comp = Completion(
            rid=req.rid,
            tokens=list(toks),
            prompt_len=len(req.prompt),
            finish_reason=reason,
            ttft_s=req.first_token_time - req.arrival_time,
            tpot_s=tpot,
            e2e_s=now - req.arrival_time,
            requeues=req.requeues,
            tenant=req.tenant,
            klass=req.klass,
            queue_s=req.admit_time - req.arrival_time,
            token_times=list(req.token_times),
        )
        self.completions[req.rid] = comp
        self.metrics.record_complete(
            now, n, comp.ttft_s, tpot, comp.e2e_s, klass=req.klass,
            queue_s=comp.queue_s, token_times=comp.token_times,
        )
        self._rec.retired += 1
        self._slot_req[slot] = None
        self._slot_tokens[slot] = []
        self._decoding.discard(slot)
        # a prefill pool's request whose FIRST token was the EOS was
        # frozen for migration before the host read it: nothing to move
        self._handoff = [h for h in self._handoff if h.slot != slot]
        self.cache.free(slot)  # slot AND its blocks return to the pool
        self._reserved -= self._worst_blocks(req)

    def snapshot_state(self) -> Dict:
        """Non-destructive restartable snapshot at a step boundary —
        the PERIODIC checkpointing path (crash consistency while the
        engine keeps serving; a kill between checkpoints only costs the
        replay of work the last snapshot already covers).

        JSON-able payload: every unfinished request's full metadata
        (prompt, seed, token budget, tenant/class, arrival, requeue
        count) — in-flight requests first in arrival order, exactly the
        order `requeue_inflight` would restore — plus the in-flight
        emitted-token ledger (the tokens a restart throws away and
        replays) and the checkpoint timestamp anchoring the
        recovery-time metric.

        `serve.drain` fires BEFORE any state is read: a transient
        injected fault aborts the snapshot with the engine untouched.
        Then what is outstanding on the device is read back, so the
        ledger counts every token dispatched. That may FINISH requests: they are in
        `completions` and not in the snapshot, so a caller that
        persists the snapshot delivers the completions first."""
        faults.fire(
            "serve.drain",
            queued=self.queue.depth,
            active=self.num_active,
        )
        self.flush("snapshot")
        inflight = sorted(
            (
                self._slot_req[s]
                for s in range(self.cache.slots)
                if self._slot_req[s] is not None
            ),
            key=lambda r: r.arrival_time,
        )
        emitted = {
            self._slot_req[s].rid: len(self._slot_tokens[s])
            for s in range(self.cache.slots)
            if self._slot_req[s] is not None
        }
        heads, tails = self.queue.snapshot_split()
        return {
            "version": 1,
            "checkpoint_time": float(self.clock()),
            "emitted": emitted,
            # "requests": engine-accepted work (in-flight + requeued) —
            # restored exempt from bounds; "queued": the submitted-tail
            # backlog — restored into the bounded, class-sheddable tails
            "requests": [r.to_state() for r in inflight + heads],
            "queued": [r.to_state() for r in tails],
        }

    def drain(self) -> Dict:
        """Stop serving at a step boundary and capture restartable
        state — the elastic-agent restart/resize path.

        The outstanding results are read back, then `snapshot_state()`
        plus the terminal half: quiesce the device lanes through the
        `serve/decode.py` drain seam (every donated
        buffer materialized — no program may still be writing the pool
        when the process exits) and requeue all in-flight work (each
        request replays token-identically from its seed, so dropping
        device state loses nothing but the replay time). The engine
        itself stays usable — a cancelled drain just keeps serving."""
        self.flush("drain")
        state = self.snapshot_state()
        (
            self._dev_lengths,
            self._dev_tokens,
            self._dev_rngs,
        ) = sync_slot_lanes(
            self._dev_lengths, self._dev_tokens, self._dev_rngs
        )
        self.requeue_inflight()
        return state

    def _note_recovery(self, now: float) -> None:
        """First emitted token after an elastic restore closes the
        recovery window (drain timestamp -> token served on the
        re-formed gang)."""
        if self._recovery_anchor is None:
            return
        restored, replayed, gen = self._recovery_meta
        self.metrics.record_recovery(
            now - self._recovery_anchor, restored, replayed, gen
        )
        self._recovery_anchor = None

    def requeue_inflight(self) -> int:
        """Drain every in-flight request (decoding AND mid-prefill) back
        to the queue HEAD in ARRIVAL order and free slots + blocks — the
        mid-stream kill/restart path. Each request replays from scratch
        off its own seed, so greedy outputs are unchanged by any number
        of requeues. Outstanding results are read back first: a request
        they complete is done, not replayed."""
        self.flush("requeue")
        inflight = sorted(
            (
                s
                for s in range(self.cache.slots)
                if self._slot_req[s] is not None
            ),
            key=lambda s: self._slot_req[s].arrival_time,
        )
        for s in reversed(inflight):
            req = self._slot_req[s]
            req.requeues += 1
            req.first_token_time = None
            self._slot_req[s] = None
            self._slot_tokens[s] = []
            self._prefilling.pop(s, None)
            self._decoding.discard(s)
            self.queue.requeue_front(req)
            self.cache.free(s)
            self._reserved -= self._worst_blocks(req)
        # frozen handoffs were in-flight too (their slots held requests)
        # — requeued above; drop the stale migration records
        self._handoff = []
        self.metrics.record_requeue(len(inflight))
        self._rec.preempted += len(inflight)
        return len(inflight)

    # -- disaggregated handoff / landing (serve/disagg/) -------------------
    def pop_handoffs(self) -> List[Handoff]:
        """Drain the frozen-handoff list (``role="prefill"``), after
        reading back what is outstanding: every record handed out holds
        its `first` token. The
        slots stay frozen — blocks pinned, lanes parked — until the
        caller exports each payload and calls `release_handoff`; an
        engine step between pop and release is safe (frozen rows are
        invalidated in `step`), but an eviction in that window makes
        the record stale, which `release_handoff` detects by request
        identity."""
        self.flush("handoff")
        out, self._handoff = self._handoff, []
        return out

    def release_handoff(self, h: Handoff) -> None:
        """Return a migrated handoff's slot + blocks to the pool —
        called AFTER the payload is durably published (store-first
        discipline: a crash between publish and release just re-sends
        identical bytes). No-op when the slot no longer holds `h.req`
        (evicted since the pop — the request is replaying anyway)."""
        self.flush("handoff")
        if self._slot_req[h.slot] is not h.req:
            return
        self._slot_req[h.slot] = None
        self._slot_tokens[h.slot] = []
        self.cache.free(h.slot)
        self._reserved -= self._worst_blocks(h.req)

    def attach_migrated(
        self, req: Request, length: int, first: int, payload
    ) -> Optional[int]:
        """Land a migrated prefill on this (decode-pool) engine: claim
        a slot, import the KV block payload
        (`serve/cache.py::import_blocks` — raw int8 + scale planes, so
        the landed pool bytes are BITWISE the prefill pool's), and seed
        the slot's lanes with the already-sampled first token and the
        carry key reconstructed from `req.seed`
        (`serve/decode.py::carry_key`). Decode then proceeds exactly as
        if this engine had prefilled locally — token-exact by
        construction. Returns the slot, or None when this engine cannot
        hold the request right now (caller retries / picks another
        replica; nothing was mutated)."""
        from .decode import carry_key

        if self.role == "prefill":
            raise DistError("prefill-pool engines cannot land migrations")
        self.flush("handoff")  # a row that is done frees its slot first
        worst = self._worst_blocks(req)
        if self.conservative_admission and (
            self._reserved + worst > self.cache.num_blocks
        ):
            return None
        slot = self.cache.allocate()
        if slot is None:
            return None
        if not self.cache.ensure_blocks(slot, length - 1):
            self.cache.free(slot)
            return None
        self.cache.import_blocks(self.cache.slot_blocks(slot), payload)
        (
            self._dev_lengths,
            self._dev_tokens,
            self._dev_rngs,
        ) = self._attach(
            self._dev_lengths,
            self._dev_tokens,
            self._dev_rngs,
            slot,
            length,
            np.int32(first),
            carry_key(req.seed),
        )
        self.cache.lengths[slot] = length
        self._slot_req[slot] = req
        self._slot_tokens[slot] = [first]
        self._decoding.add(slot)
        self._reserved += worst
        self.metrics.record_admit()
        now = self.clock()
        if req.first_token_time is None:
            # migration meta normally carries the prefill-side stamp;
            # fall back to "now" so TPOT stays finite either way
            req.first_token_time = now
        # admitted HERE now, holding the one token the prefill pool stamped
        req.admit_time, req.token_times = now, [req.first_token_time]
        self._rec.admitted += 1
        self._note_recovery(now)
        return slot

    # -- introspection -----------------------------------------------------
    @property
    def num_active(self) -> int:
        return len(self.cache.active_slots)

    @property
    def pending(self) -> int:
        return self.queue.depth + self.num_active
