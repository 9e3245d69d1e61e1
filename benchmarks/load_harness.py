"""Open-loop load harness + the `serve_autoscale` row (ISSUE 15).

The scale proof for the closed-loop autoscaler: a BURSTY multi-tenant
trace at 10-100x the other serve benches' request counts, replayed
OPEN-LOOP — every request carries a fixed arrival timestamp drawn from
a diurnal-style rate curve (trough -> `peak_x` x trough -> trough), and
arrivals never wait on completions, so a saturated gang sees the
backlog a real front door would see instead of a closed loop's
self-throttling. The whole harness runs on a VIRTUAL clock (every
router step advances time by a fixed `step_cost_s`; every engine,
router, and controller shares the clock), which makes replays
deterministic and replayable by seed: same seed -> same trace, same
metric windows, same controller decisions, same resizes.

Three replays of the SAME trace:

* **autoscaled** — `ServeRouter` starting at 1 replica under the
  `Autoscaler` (hysteresis bands + breach streaks + cooldowns +
  max-step clamp). The controller must ride the swing up and back
  down; the row requires gold-class SLO attainment >= 0.99 end to end
  AND at least one scale-out and one scale-in (a gang that never
  resized proves nothing).
* **static peak** — the same trace on a FIXED gang provisioned at the
  autoscaled run's peak width, the capacity a team without a
  controller must buy for the whole day. Chip-seconds (the router's
  `replicas x virtual-time` integral) against the autoscaled run is
  the money figure: `chip_seconds_saved_frac`.
* **chaos** — the autoscaled replay with transient faults injected at
  the `serve.scale_out` AND `serve.scale_in` seams mid-swing. Both
  fire BEFORE any state moves, so each aborted resize leaves the gang
  at a consistent size and the controller retries next poll; the
  harness asserts the chaos run's served tokens are IDENTICAL per
  request to the uninterrupted autoscaled reference (replay-from-seed
  makes token identity schedule-independent — the resize machinery
  must keep it that way).

Tenancy shape: every request is `<tenant preamble> + <unique suffix>`
with `prefix_cache=True` engines, so the router's scope affinity is
load-bearing — a tenant's preamble stays hot on one replica and the
prefix hit rate is reported alongside.

Usage: python benchmarks/load_harness.py [--preset tiny|small]
    [--requests 0 (auto from duration)] [--duration 60] [--peak-x 10]
    [--tenants 6] [--slots 4] [--max-replicas 6] [--seed 0]
    [--step-cost-ms 50] [--no-chaos]

Registered in benchmarks/run_all.py as `serve_autoscale` (quick
hermetic + full); on TPU the record self-persists into
chiprun_out/bench_results.json like every serve row.
"""

from __future__ import annotations

import argparse
import math
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

PRESETS = {
    "tiny": dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4),
    "small": dict(vocab_size=32000, d_model=256, n_layers=4, n_heads=8),
}

PREAMBLE = 12  # shared per-tenant prefix tokens (the affinity payload)
SUFFIX = (4, 9)  # unique per-request tail tokens (half-open)
NEW = (3, 8)  # decode budgets (half-open)
GOLD_SLO_S = 1.0  # virtual seconds; ~20 step-times of queueing headroom


def make_trace(
    seed: int,
    duration_s: float,
    peak_x: float,
    requests: int,
    tenants: int,
    vocab: int,
    gold_frac: float = 0.5,
):
    """Deterministic open-loop trace: `requests` arrival events over
    `duration_s` virtual seconds from the diurnal rate

        rate(t) = base * (1 + (peak_x - 1) * sin(pi * t / D)^2)

    (trough at both ends, one `peak_x`-times-trough peak mid-trace),
    sampled by inverse-CDF so the SAME seed replays the SAME
    timestamps. Each event carries tenant, class, prompt (tenant
    preamble + unique suffix), budget, and its own sampling seed —
    everything a replay (or a post-resize re-replay) needs."""
    import numpy as np

    gen = np.random.default_rng(seed)
    # inverse-CDF sampling of the normalized rate density on a grid
    grid = np.linspace(0.0, duration_s, 4096)
    dens = 1.0 + (peak_x - 1.0) * np.sin(math.pi * grid / duration_s) ** 2
    cum = np.concatenate([[0.0], np.cumsum((dens[1:] + dens[:-1]) / 2)])
    cum /= cum[-1]
    arrivals = np.sort(np.interp(gen.uniform(size=requests), cum, grid))
    preambles = [
        gen.integers(0, vocab, (PREAMBLE,)).astype(np.int32)
        for _ in range(tenants)
    ]
    events = []
    for i, arr in enumerate(arrivals):
        ten = int(gen.integers(0, tenants))
        suffix = gen.integers(
            0, vocab, (int(gen.integers(*SUFFIX)),)
        ).astype(np.int32)
        events.append(
            {
                "arrival": float(arr),
                "rid": f"r{i}",
                "tenant": f"ten{ten}",
                "klass": "gold" if gen.uniform() < gold_frac else "bronze",
                "prompt": np.concatenate([preambles[ten], suffix]),
                "budget": int(gen.integers(*NEW)),
                "seed": i,
            }
        )
    return events


def replay(
    events,
    router,
    clock_cell,
    step_cost_s: float,
    autoscaler=None,
    poll_every_s: float = 0.5,
    max_steps: int = 200_000,
):
    """Open-loop replay on the virtual clock: submit everything whose
    timestamp has passed, step the gang once (one step-time regardless
    of width — replicas are parallel hardware), advance time, poll the
    controller on its interval. Runs until the trace is exhausted AND
    the gang drains. Returns the number of router steps taken."""
    i = 0
    next_poll = 0.0
    steps = 0
    while True:
        now = clock_cell[0]
        while i < len(events) and events[i]["arrival"] <= now:
            ev = events[i]
            router.submit(
                ev["prompt"],
                ev["budget"],
                rid=ev["rid"],
                seed=ev["seed"],
                arrival_time=ev["arrival"],
                tenant=ev["tenant"],
                klass=ev["klass"],
            )
            i += 1
        if autoscaler is not None and now >= next_poll:
            autoscaler.poll()
            next_poll = now + poll_every_s
        busy = router.step()
        clock_cell[0] += step_cost_s
        steps += 1
        if steps > max_steps:
            raise RuntimeError(
                f"harness did not drain within {max_steps} steps "
                f"(submitted {i}/{len(events)})"
            )
        if i >= len(events) and not busy:
            return steps


def run_gang(args):
    """``--gang``: the PROCESS-level replay (ISSUE 16) — a real
    `LocalElasticAgent` gang of serve worker daemons
    (`examples/serve_worker/main.py`) under live wall-clock traffic,
    with the PR 14 `Autoscaler` driving `request_resize` through
    `ElasticGangScaler`. Every completion is checked token-exact
    against an uninterrupted in-process reference engine — resizes,
    drains, and restores must be invisible in the tokens. Not
    registered in run_all (wall-clock, multi-process); this is the
    operator's smoke for a worker deployment."""
    import os
    import socket
    import threading
    import time as wall

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit
    from pytorch_distributed_example_tpu.elastic.agent import (
        LocalElasticAgent,
        WorkerSpec,
    )
    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_example_tpu.serve import (
        AutoscalePolicy,
        Autoscaler,
        ServeEngine,
    )
    from pytorch_distributed_example_tpu.serve.worker import (
        ElasticGangScaler,
        GangRouter,
        wait_registered,
    )
    from pytorch_distributed_example_tpu.store import TCPStore

    # worker geometry = the entrypoint's defaults (deterministic params
    # from seed 0 on every rank, every generation)
    vocab, max_seq = 64, 32
    duration = min(args.duration, 30.0)
    events = make_trace(
        args.seed, duration, args.peak_x,
        args.requests or int(duration * 3), args.tenants, vocab,
    )
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    os.environ["TDX_SERVE_CPU"] = "1"
    width0 = min(2, args.max_replicas)
    spec = WorkerSpec(
        entrypoint=[
            "examples/serve_worker/main.py", "--slots", str(args.slots),
        ],
        # capacity is the CEILING resizes clamp to; the gang FORMS at
        # width0 (active_nproc below) so the autoscaler has headroom
        # in both directions
        nproc_per_node=args.max_replicas,
        min_nproc=1,
        master_port=port,
        max_restarts=10,
        serve_drain_grace_s=10.0,
    )
    agent = LocalElasticAgent(spec)
    agent.active_nproc = width0
    res = {}
    th = threading.Thread(
        target=lambda: res.update(run=agent.run()), daemon=True
    )
    th.start()
    store = TCPStore("127.0.0.1", port, is_master=False, timeout=60.0)
    wait_registered(store, 0, width0, timeout=120.0)
    router = GangRouter(store)
    scaler = Autoscaler(
        ElasticGangScaler(router, "127.0.0.1", port),
        AutoscalePolicy(
            slo_floor=0.99,
            queue_high=float(args.slots),
            queue_low=0.5,
            occupancy_low=0.5,
            breach_polls=2,
            cooldown_out_s=3.0,
            cooldown_in_s=10.0,
            max_step=1,
            min_replicas=1,
            max_replicas=args.max_replicas,
        ),
        window_s=5.0,
    )
    t0 = wall.monotonic()
    try:
        i, next_poll = 0, 0.0
        while i < len(events):
            now = wall.monotonic() - t0
            while i < len(events) and events[i]["arrival"] <= now:
                ev = events[i]
                # gang workers run classless engines (the entrypoint's
                # default) — tenancy rides along, class SLOs stay virtual
                router.submit(
                    ev["prompt"], ev["budget"], rid=ev["rid"],
                    seed=ev["seed"], tenant=ev["tenant"],
                )
                i += 1
            if now >= next_poll:
                scaler.poll()
                next_poll = now + 1.0
            wall.sleep(0.02)
        out = router.wait_all(timeout=240.0)
        span = wall.monotonic() - t0
    finally:
        # even on failure: drop the sentinel so no worker outlives us
        router.shutdown()
        th.join(timeout=60.0)

    # uninterrupted single-engine reference: resizes must be invisible
    cfg = TransformerConfig(
        vocab_size=vocab, d_model=32, n_layers=2, n_heads=4,
        max_seq_len=max_seq, use_flash=False,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    ref = ServeEngine(model, params, slots=args.slots)
    for ev in events:
        ref.submit(
            np.asarray(ev["prompt"]), ev["budget"], rid=ev["rid"],
            seed=ev["seed"], tenant=ev["tenant"],
        )
    ref_out = {r: list(c.tokens) for r, c in ref.run(500_000).items()}
    mismatched = [r for r in ref_out if out.get(r) != ref_out[r]]
    assert not mismatched, (
        f"{len(mismatched)} requests token-diverged across the gang "
        f"(e.g. {mismatched[:3]})"
    )
    run_res = res.get("run")
    emit(
        "serve_gang_token_exact_frac",
        1.0,
        "frac",
        requests=len(events),
        duration_wall_s=round(span, 2),
        generations=getattr(run_res, "restarts", None),
        resize_decisions=len(
            [d for d in scaler.decisions if d.action != "hold"]
        ),
        final_state=str(getattr(run_res, "state", "?")),
        slots=args.slots,
        max_replicas=args.max_replicas,
        seed=args.seed,
        timing="wall_clock",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    ap.add_argument(
        "--requests", type=int, default=0,
        help="0 = sized from duration (~33/s mean at peak-x 10)",
    )
    ap.add_argument("--duration", type=float, default=60.0,
                    help="virtual trace seconds")
    ap.add_argument("--peak-x", type=float, default=10.0)
    ap.add_argument("--tenants", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-replicas", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--step-cost-ms", type=float, default=50.0)
    ap.add_argument("--no-chaos", action="store_true")
    ap.add_argument("--gang", action="store_true",
                    help="process-level mode: a real elastic-agent gang "
                         "of serve worker daemons under wall-clock "
                         "traffic, autoscaler driving request_resize "
                         "(ISSUE 16; not part of run_all)")
    args = ap.parse_args()
    if args.gang:
        run_gang(args)
        return

    import jax
    import jax.numpy as jnp

    from benchmarks.common import emit, on_tpu, persist_result
    from pytorch_distributed_example_tpu import faults
    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_example_tpu.serve import (
        AutoscalePolicy,
        Autoscaler,
        ClassSpec,
        ServeEngine,
        ServeMetrics,
        ServeRouter,
    )

    step_cost_s = args.step_cost_ms / 1e3
    max_seq = PREAMBLE + SUFFIX[1] + NEW[1] + 2
    cfg = TransformerConfig(
        max_seq_len=max_seq, use_flash=False, **PRESETS[args.preset]
    )
    model = TransformerLM(cfg)
    import numpy as np

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    classes = {
        "gold": ClassSpec(priority=0, weight=4, ttft_slo_s=GOLD_SLO_S),
        "bronze": ClassSpec(priority=1, weight=1, ttft_slo_s=8.0),
    }
    requests = args.requests or int(
        args.duration * 6.0 * (1 + (args.peak_x - 1) / 2)
    )
    events = make_trace(
        args.seed, args.duration, args.peak_x, requests,
        args.tenants, cfg.vocab_size,
    )

    def run(autoscaled: bool, replicas: int):
        t = [0.0]

        def factory(rid):
            return ServeEngine(
                model, params, slots=args.slots, min_bucket=4,
                classes=classes, clock=lambda: t[0], prefix_cache=True,
                metrics=ServeMetrics(
                    clock=lambda: t[0], slots=args.slots,
                    classes=classes, window_s=5.0,
                ),
            )

        router = ServeRouter(
            factory, replicas=replicas, classes=classes,
            clock=lambda: t[0],
        )
        scaler = None
        if autoscaled:
            scaler = Autoscaler(
                router,
                AutoscalePolicy(
                    target_class="gold",
                    slo_floor=0.99,
                    # queue pressure is the EARLY signal: a backlog of
                    # one slot-batch per replica costs ~0.3 virtual
                    # seconds of TTFT — scale out well before the SLO
                    # itself breaks
                    queue_high=float(args.slots),
                    queue_low=0.5,
                    occupancy_low=0.6,
                    breach_polls=2,
                    cooldown_out_s=1.0,
                    cooldown_in_s=8.0,
                    max_step=1,
                    min_replicas=1,
                    max_replicas=args.max_replicas,
                ),
                clock=lambda: t[0],
                window_s=5.0,
            )
        steps = replay(
            events, router, t, step_cost_s, autoscaler=scaler,
        )
        return router, scaler, steps, t[0]

    def gold_attainment(router):
        gold = [
            c for c in router.completions.values() if c.klass == "gold"
        ]
        met = sum(1 for c in gold if c.ttft_s <= GOLD_SLO_S)
        return met / len(gold) if gold else 0.0, len(gold)

    # -- autoscaled reference ----------------------------------------------
    faults.clear_plan()
    auto, scaler, auto_steps, auto_span = run(True, replicas=1)
    assert len(auto.completions) == len(events), (
        f"autoscaled run lost requests: {len(auto.completions)}/"
        f"{len(events)}"
    )
    att_auto, n_gold = gold_attainment(auto)
    widths = [e.replicas_after for e in auto.events]
    peak = max(widths + [1])
    outs = sum(1 for e in auto.events if e.kind == "add")
    ins = sum(1 for e in auto.events if e.kind == "remove")
    assert outs >= 1 and ins >= 1, (
        f"controller never exercised both directions (out={outs}, "
        f"in={ins}) — the swing row would be vacuous"
    )

    # -- static peak provisioning ------------------------------------------
    static, _, _, static_span = run(False, replicas=peak)
    att_static, _ = gold_attainment(static)
    assert static.completions.keys() == auto.completions.keys()
    for rid, comp in auto.completions.items():
        assert static.completions[rid].tokens == comp.tokens, (
            f"{rid}: replica width changed served tokens — replay bug"
        )

    # -- chaos: transient faults at both scale seams mid-swing -------------
    chaos_exact = None
    if not args.no_chaos:
        faults.install_plan(
            [
                {"point": "serve.scale_out", "action": "reset",
                 "after": 2},
                {"point": "serve.scale_in", "action": "drop",
                 "after": 1},
            ],
            export_env=False,
        )
        try:
            chaos, chaos_scaler, _, _ = run(True, replicas=1)
        finally:
            faults.clear_plan()
        aborted = [
            d
            for d in chaos_scaler.decisions
            if d.outcome.startswith("aborted")
        ]
        assert aborted, "chaos plan never hit a scale seam"
        assert chaos.completions.keys() == auto.completions.keys()
        for rid, comp in auto.completions.items():
            assert chaos.completions[rid].tokens == comp.tokens, (
                f"{rid}: mid-resize fault changed served tokens"
            )
        chaos_exact = True

    # realized swing: arrival-rate max/mean-trough over 1/8-duration bins
    bins = np.histogram(
        [e["arrival"] for e in events],
        bins=8,
        range=(0.0, args.duration),
    )[0]
    trough = max(min(bins[0], bins[-1]), 1)
    snap = auto.snapshot()
    saved = 1.0 - auto.chip_seconds / max(static.chip_seconds, 1e-9)
    hits = sum(v["prefix_hits"] for v in snap["replicas"].values())
    misses = sum(v["prefix_misses"] for v in snap["replicas"].values())
    rec = emit(
        "serve_autoscale_gold_slo_attainment",
        round(att_auto, 4),
        "frac",
        target_attainment=0.99,
        gold_completed=n_gold,
        requests=len(events),
        swing_design_x=args.peak_x,
        swing_realized_x=round(float(max(bins)) / trough, 2),
        # the money figure: chip-seconds the controller did not burn
        chip_seconds_auto=round(auto.chip_seconds, 2),
        chip_seconds_static_peak=round(static.chip_seconds, 2),
        chip_seconds_saved_frac=round(saved, 4),
        peak_replicas=peak,
        scale_outs=outs,
        scale_ins=ins,
        resizes=scaler.resizes,
        gold_slo_attainment_static=round(att_static, 4),
        token_identical_vs_static=True,
        chaos_midswing_token_exact=chaos_exact,
        # affinity evidence across the SURVIVING replicas (removed
        # replicas take their counters with them): tenant preambles
        # stay hot on their bound replica
        prefix_hit_rate_live=round(
            hits / (hits + misses) if (hits + misses) else 0.0, 4
        ),
        duration_virtual_s=args.duration,
        step_cost_ms=args.step_cost_ms,
        slots=args.slots,
        tenants=args.tenants,
        max_replicas=args.max_replicas,
        seed=args.seed,
        preset=args.preset,
        platform=jax.devices()[0].platform,
        device_kind=getattr(jax.devices()[0], "device_kind", "?"),
        timing="virtual_clock",
    )
    if on_tpu():
        persist_result("serve_autoscale", rec)


if __name__ == "__main__":
    main()
