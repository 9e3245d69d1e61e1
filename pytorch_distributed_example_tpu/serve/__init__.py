"""Continuous-batching serve subsystem.

The inference half of the north star: a PAGED block-pool KV cache with
per-request block tables (`cache.py` — memory tracks live tokens, not
slots x max_len), a single compiled paged decode step plus chunked
prefill programs (`decode.py`), a scheduler with mid-stream
retire-and-backfill, prefill/decode interleaving, pool-pressure
preemption and optional tensor-parallel placement over a device mesh
(`engine.py`), a bounded request queue with explicit shed (`queue.py`),
bucketed prefill shapes (`bucketing.py`), and a metrics block — cache-
pool utilization included — exposed over the debug HTTP frontend
(`metrics.py`). The serve cells of `bench_matrix/` measure it on the
chip. What a `step()` call did is `engine.last_step` (a `StepRecord`:
what it admitted, dispatched, read back and retired, and the host
seconds of each of its phases), written onto the host line of a
profiler trace as `serve:*` annotations and reduced on `/serve` to a
`host` block (`window.host`: ms a phase a call, `wait_share`, the
longest call with its split).

Three families of table, one manager (`cache.py::PagedKVCache`; the
programs take one block table a kind of layer the model has,
`cfg.cache_kinds`): refcounted BLOCKS (every token kept, paged and
shareable), WINDOW blocks (a window layer's last `window + chunk` keys in
a second pool, recycled while the request runs) and ONE STATE BLOCK a
request (a layer whose mixer keeps a fixed state keeps no K/V and gets no
K/V pool; the block's leaves are the mixer's own; held from admission to
retirement, zero for a row at position 0, untouched by padding and parked
lanes). Which family a kind of layer rides, what its pool holds, which
path it reports and what the engine refuses with it (`prefix_cache`,
`kv_quant`, `mesh=`, disaggregated roles, `precompiled=`) is ONE record a
kind, in `kinds.py`; a preempted request of a model that keeps a window
or a state prefills again from 0.

Prefix sharing (ISSUE 12): the pool's physical blocks are refcounted
with copy-on-write divergence (`cache.py`), and a radix prefix index
(`prefix.py`) maps a new request's longest cached prompt prefix to
already-filled blocks — admission attaches them by reference and
prefill starts at the first uncached position, so TTFT and pool bytes
scale with UNIQUE tokens. Cross-tenant sharing is opt-in per
`ClassSpec.share_prefix`.

Multi-tenant + elastic (ROADMAP item 5): priority classes with
weighted admission, class-ordered overload shedding and cross-class
preemption (`queue.py` / `engine.py` ``classes=``), and drain /
checkpoint / restore of the serving plane through the incarnation-
scoped store so an elastic-agent restart or resize replays interrupted
requests token-identically (`elastic.py`), with per-class and
recovery-time metrics on ``/serve``.

Closed-loop autoscaling (ISSUE 15): a data-parallel router across
engine replicas with session affinity on the radix prefix scopes
(`router.py` — a tenant's shared blocks stay hot on one replica;
replica loss re-routes and replays) and an SLO controller
(`autoscale.py`) that polls ROLLING-WINDOW attainment / queue depth /
pool pressure (`metrics.py::window_view`) and drives drain-backed
scale-out/scale-in with hysteresis bands, breach streaks, cooldowns,
and a max-step clamp — every decision logged with the metric view
that justified it, `TDX_AUTOSCALE_FORCE` for operators.
"""

from .bucketing import bucket_for, bucket_lengths  # noqa: F401
from .cache import (  # noqa: F401
    PagedKVCache,
    init_paged_cache,
)
from .decode import (  # noqa: F401
    carry_key,
    kernel_layers,
    layer_paths,
    paged_programs,
    sync_slot_lanes,
)
from .elastic import (  # noqa: F401
    drain_requested,
    gc_serve_state,
    load_serve_state,
    restore_into,
    save_serve_state,
    signal_drain,
)
from .autoscale import (  # noqa: F401
    AutoscalePolicy,
    Autoscaler,
    Decision,
)
from .engine import PHASES, ServeEngine, StepRecord  # noqa: F401
from .metrics import ServeMetrics, percentile  # noqa: F401
from .prefix import PrefixIndex, prefix_scope  # noqa: F401
from .router import ScaleEvent, ServeRouter  # noqa: F401
from .worker import (  # noqa: F401
    ElasticGangScaler,
    GangRouter,
    ServeWorker,
    wait_registered,
)
from .prewarm import (  # noqa: F401
    GeometrySpec,
    enable_compile_cache,
    prewarm_engine_programs,
    reachable_geometries,
)
from .queue import (  # noqa: F401
    ClassSpec,
    Completion,
    QueueFullError,
    Request,
    RequestQueue,
)
