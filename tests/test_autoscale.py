"""Closed-loop SLO autoscaling (ISSUE 15): rolling-window metrics, the
DP serve router's prefix-scope affinity + drain-backed scale events,
the hysteresis/cooldown controller, the scale-seam chaos contracts, and
the open-loop load harness — all on fake clocks, fully deterministic.

Controller-logic cases (blips, band-edge oscillation, cooldowns,
max-step, force overrides) drive the Autoscaler against a scripted
router stub so each edge is exact; everything that claims token
identity runs real engines and compares against uncontended references.
"""

import numpy as np
import pytest

from pytorch_distributed_example_tpu import faults
from pytorch_distributed_example_tpu.serve import (
    AutoscalePolicy,
    Autoscaler,
    ClassSpec,
    ServeMetrics,
    ServeRouter,
    prefix_scope,
)

CLASSES = {
    "gold": ClassSpec(priority=0, weight=4, ttft_slo_s=1.0),
    "bronze": ClassSpec(priority=1, weight=1),
}


@pytest.fixture()
def no_fault_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


def _model(max_seq_len=32):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )

    cfg = TransformerConfig(
        vocab_size=64,
        d_model=32,
        n_layers=2,
        n_heads=4,
        max_seq_len=max_seq_len,
        use_flash=False,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    return model, params


def _prompts(*lens, seed=0, vocab=64):
    gen = np.random.default_rng(seed)
    return [gen.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


def _router(model, params, t, replicas=1, classes=CLASSES, **kw):
    from pytorch_distributed_example_tpu.serve import ServeEngine

    def factory(rid):
        return ServeEngine(
            model, params, slots=2, min_bucket=4, classes=classes,
            clock=lambda: t[0], prefix_cache=True,
            metrics=ServeMetrics(
                clock=lambda: t[0], slots=2, classes=classes,
                window_s=10.0,
            ),
        )

    return ServeRouter(
        factory, replicas=replicas, classes=classes,
        clock=lambda: t[0], **kw,
    )


# ---------------------------------------------------------------------------
# rolling-window metrics
# ---------------------------------------------------------------------------


class TestWindowedMetrics:
    def test_window_sees_recovery_lifetime_does_not(self):
        """The reason the controller must NOT steer on lifetime
        aggregates: after an early bad patch, lifetime attainment stays
        poisoned while the trailing window reports the true recent
        state (and the mirror image: a fresh breach is invisible to a
        long healthy lifetime)."""
        t = [0.0]
        m = ServeMetrics(
            clock=lambda: t[0], slots=4, classes=CLASSES, window_s=10.0
        )
        for i in range(5):  # t in [0, 5): every gold completion late
            t[0] = float(i)
            m.record_complete(t[0], 4, ttft_s=5.0, tpot_s=0.1,
                              e2e_s=5.5, klass="gold")
        for i in range(5):  # t in [20, 25): all healthy
            t[0] = 20.0 + i
            m.record_complete(t[0], 4, ttft_s=0.2, tpot_s=0.1,
                              e2e_s=0.7, klass="gold")
        snap = m.snapshot()
        assert snap["classes"]["gold"]["slo_attainment"] == 0.5  # lifetime
        win = m.window_view(window_s=10.0, now=25.0)
        g = win["classes"]["gold"]
        assert g["slo_attainment"] == 1.0  # the window forgave t<5
        assert g["slo_met"] == 5 and g["slo_n"] == 5
        # replaying the breach window shows the breach, not the recovery
        g_old = m.window_view(window_s=10.0, now=5.0)["classes"]["gold"]
        assert g_old["slo_attainment"] == 0.0

    def test_window_no_evidence_is_none_not_perfect(self):
        t = [100.0]
        m = ServeMetrics(
            clock=lambda: t[0], slots=4, classes=CLASSES, window_s=5.0
        )
        win = m.window_view()
        assert win["classes"]["gold"]["slo_attainment"] is None
        # a class with no SLO configured never gets a verdict either
        m.record_complete(100.0, 2, 0.1, 0.1, 0.3, klass="bronze")
        win = m.window_view()
        assert win["classes"]["bronze"]["slo_attainment"] is None
        assert win["classes"]["bronze"]["completed"] == 1

    def test_window_queue_and_shed_samples_age_out(self):
        t = [0.0]
        m = ServeMetrics(
            clock=lambda: t[0], slots=4, classes=CLASSES, window_s=10.0
        )
        m.record_step(queue_depth=50, slots_active=4)
        m.record_shed("bronze")
        t[0] = 100.0
        m.record_step(queue_depth=2, slots_active=1)
        win = m.window_view(window_s=10.0)
        assert win["queue_depth_mean"] == 2.0
        assert win["queue_depth_max"] == 2
        assert win["occupancy_mean"] == 0.25
        assert win["classes"]["bronze"]["shed"] == 0  # aged out
        wide = m.window_view(window_s=1000.0)
        assert wide["queue_depth_max"] == 50
        assert wide["classes"]["bronze"]["shed"] == 1

    def test_snapshot_exposes_window_block(self):
        m = ServeMetrics(slots=2, classes=CLASSES)
        snap = m.snapshot()
        assert "window" in snap
        assert snap["window"]["window_s"] == 30.0  # the default
        assert "queue_depth_mean" in snap["window"]


# ---------------------------------------------------------------------------
# router: affinity + elastic scale events
# ---------------------------------------------------------------------------


class TestRouterAffinity:
    def test_scope_key_is_shared_with_prefix_cache(self):
        """Affinity and the radix index key on the SAME function."""
        assert prefix_scope(CLASSES, "gold", "acme") == ("tenant", "acme")
        shared = {
            "gold": ClassSpec(priority=0, share_prefix=True),
        }
        assert prefix_scope(shared, "gold", "acme") == "*"
        assert prefix_scope(None, "", "acme") == ("tenant", "acme")

    def test_tenant_sticks_to_one_replica(self, no_fault_plan):
        model, params = _model()
        t = [0.0]
        r = _router(model, params, t, replicas=3)
        p = _prompts(5, 5, 5, 5)
        for i in range(4):
            r.submit(p[i], 2, rid=f"a{i}", tenant="acme", klass="gold")
            r.submit(p[i], 2, rid=f"b{i}", tenant="bobco", klass="gold")
        homes = {
            rid: rep
            for rid, (rep, _) in r._outstanding.items()
        }
        assert len({homes[f"a{i}"] for i in range(4)}) == 1
        assert len({homes[f"b{i}"] for i in range(4)}) == 1
        while r.step():
            t[0] += 0.5
        assert len(r.completions) == 8

    def test_rebalance_rebinds_under_skew(self, no_fault_plan):
        """Affinity yields when the bound replica's backlog exceeds the
        coldest replica's by more than rebalance_backlog — the width-1
        cold-start case: scopes bound to replica 0 must migrate once
        new replicas appear, or scale-out adds idle capacity."""
        model, params = _model()
        t = [0.0]
        r = _router(model, params, t, replicas=1, rebalance_backlog=3)
        p = _prompts(*([5] * 12))
        for i in range(6):
            r.submit(p[i], 4, rid=f"x{i}", tenant="acme", klass="bronze")
        r.add_replica()
        for i in range(6, 12):
            r.submit(p[i], 4, rid=f"x{i}", tenant="acme", klass="bronze")
        assert r.rebinds >= 1
        reps = {rep for _, (rep, _) in r._outstanding.items()}
        assert len(reps) == 2  # the tenant spilled onto the new replica
        while r.step():
            t[0] += 0.5
        assert len(r.completions) == 12

    def test_routing_is_deterministic(self, no_fault_plan):
        model, params = _model()

        def run():
            t = [0.0]
            r = _router(model, params, t, replicas=2)
            p = _prompts(5, 6, 4, 7, 5, 6)
            for i in range(6):
                r.submit(
                    p[i], 3, rid=f"r{i}", seed=i,
                    tenant=f"ten{i % 3}", klass="gold",
                )
            assign = {
                rid: rep for rid, (rep, _) in r._outstanding.items()
            }
            while r.step():
                t[0] += 0.5
            return assign, {
                k: v.tokens for k, v in r.completions.items()
            }

        a1, out1 = run()
        a2, out2 = run()
        assert a1 == a2
        assert out1 == out2


class TestRouterElastic:
    def _reference(self, model, params, prompts, budgets):
        """Single uncontended engine — the token yardstick."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        eng = ServeEngine(
            model, params, slots=2, min_bucket=4, classes=CLASSES
        )
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            eng.submit(p, b, rid=f"r{i}", seed=i, klass="gold")
        return eng.run(max_steps=800)

    def test_scale_in_drains_and_redistributes_token_exact(
        self, no_fault_plan
    ):
        """Mid-flight scale-in: the victim's in-flight + queued work
        lands in survivors through the drain snapshot and finishes
        token-identically; nothing is lost, nothing double-served."""
        model, params = _model()
        prompts = _prompts(5, 6, 4, 7, 5, 6)
        budgets = [4, 5, 3, 4, 5, 3]
        ref = self._reference(model, params, prompts, budgets)

        t = [0.0]
        r = _router(model, params, t, replicas=2)
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            r.submit(
                p, b, rid=f"r{i}", seed=i, tenant=f"ten{i % 2}",
                klass="gold",
            )
        for _ in range(2):  # both replicas mid-flight
            r.step()
            t[0] += 0.5
        assert r.num_replicas == 2
        victim = r.remove_replica()
        assert r.num_replicas == 1
        assert any(
            e.kind == "remove" and e.replica_id == victim
            for e in r.events
        )
        while r.step():
            t[0] += 0.5
        assert set(r.completions) == set(ref)
        for rid in ref:
            assert r.completions[rid].tokens == ref[rid].tokens, rid

    def test_last_replica_not_removable(self, no_fault_plan):
        model, params = _model()
        t = [0.0]
        r = _router(model, params, t, replicas=1)
        with pytest.raises(ValueError, match="last replica"):
            r.remove_replica()

    def test_scale_in_never_discards_undrained_work(self, no_fault_plan):
        """The victim holds the ONLY live copy of its un-drained
        in-flight work; removal must land every one of those requests
        in a survivor (ledger + queues), never on the floor."""
        model, params = _model()
        prompts = _prompts(5, 6, 4, 7)
        t = [0.0]
        r = _router(model, params, t, replicas=2)
        for i, p in enumerate(prompts):
            r.submit(
                p, 6, rid=f"r{i}", seed=i, tenant=f"ten{i}",
                klass="gold",
            )
        r.step()  # work in flight on both replicas
        before = set(r._outstanding)
        victim = r.remove_replica()
        after = {
            rid: rep for rid, (rep, _) in r._outstanding.items()
        }
        assert set(after) == before  # every request still tracked
        assert victim not in set(after.values())
        out = r.run(max_steps=800)
        assert set(out) == before

    def test_scale_in_settles_shed_victims_not_strands_them(
        self, no_fault_plan
    ):
        """REGRESSION (review): a class-shed request lives in neither
        the drain snapshot's "requests" nor its "queued" — it never ran
        and never will. Removing (or losing) a replica before the next
        step()'s collect must still settle it out of the router ledger,
        or `pending` never reaches zero; a loss must NOT resubmit it
        either (it was reported displaced)."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()

        def bounded_router(t):
            def factory(rid):
                return ServeEngine(
                    model, params, slots=1, min_bucket=4,
                    classes=CLASSES, clock=lambda: t[0],
                    max_queue_depth=1,
                )

            return ServeRouter(
                factory, replicas=2, classes=CLASSES,
                clock=lambda: t[0],
            )

        p = _prompts(5, 6, 4)
        for scale_op in ("remove", "lose"):
            t = [0.0]
            r = bounded_router(t)
            # same tenant -> same replica; b0 takes the slot, b1 fills
            # the bounded tail, and the gold submit displaces b1 into
            # that engine's shed_requests
            r.submit(p[0], 4, rid="b0", tenant="acme", klass="bronze")
            r.step()  # b0 admitted into the only slot
            r.submit(p[1], 4, rid="b1", tenant="acme", klass="bronze")
            r.submit(p[2], 4, rid="g0", tenant="acme", klass="gold")
            victim = next(
                rep for _, (rep, _) in r._outstanding.items()
            )
            # the scale event runs BEFORE any step() could collect
            if scale_op == "remove":
                r.remove_replica(victim)
            else:
                r.lose_replica(victim)
            assert "b1" not in r._outstanding  # settled, not stranded
            out = r.run(max_steps=500)
            assert r.pending == 0
            assert "b1" not in out  # shed stays shed — never re-served
            assert {"b0", "g0"} <= set(out)

    def test_scale_in_seals_snapshot_into_store(self, no_fault_plan):
        from pytorch_distributed_example_tpu.serve.elastic import (
            load_serve_state,
        )
        from pytorch_distributed_example_tpu.store import HashStore

        model, params = _model()
        t = [0.0]
        store = HashStore(timeout=1.0)
        r = _router(model, params, t, replicas=2, store=store)
        p = _prompts(5, 6)
        r.submit(p[0], 4, rid="r0", tenant="a", klass="gold")
        r.submit(p[1], 4, rid="r1", tenant="b", klass="gold")
        r.step()
        victim = r.remove_replica()
        st, gen = load_serve_state(
            store, key_prefix=f"serve/replica{victim}"
        )
        assert gen == 1 and st is not None
        names = {d["rid"] for d in st["requests"]} | {
            d["rid"] for d in st["queued"]
        }
        assert names <= {"r0", "r1"}
        r.run(max_steps=500)

    def test_replica_loss_reroutes_and_replays(self, no_fault_plan):
        """Abrupt loss (no drain): outstanding work re-routes to
        survivors and replays token-identically against a cold prefix
        cache — the tenant sees latency, not failures."""
        model, params = _model()
        prompts = _prompts(5, 6, 4, 7, 5, 6)
        budgets = [4, 5, 3, 4, 5, 3]
        ref = self._reference(model, params, prompts, budgets)

        t = [0.0]
        r = _router(model, params, t, replicas=2)
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            r.submit(
                p, b, rid=f"r{i}", seed=i, tenant=f"ten{i % 2}",
                klass="gold",
            )
        for _ in range(2):
            r.step()
            t[0] += 0.5
        lost = r.replica_ids()[0]
        moved = r.lose_replica(lost)
        assert moved >= 1
        assert lost not in r.replica_ids()
        while r.step():
            t[0] += 0.5
        assert set(r.completions) == set(ref)
        for rid in ref:
            assert r.completions[rid].tokens == ref[rid].tokens, rid
        # the lost replica's scopes were unbound and rebound live
        assert all(
            rep in r.replica_ids() for rep in r._affinity.values()
        )


# ---------------------------------------------------------------------------
# controller logic against a scripted router stub
# ---------------------------------------------------------------------------


class _StubRouter:
    """Deterministic metric playback + scale-op counting — the
    controller's contract surface, nothing else."""

    def __init__(self, views, replicas=2):
        self.views = views  # list of per-poll pressure dicts
        self.i = 0
        self.n = replicas
        self.adds = 0
        self.removes = 0

    def window_view(self, window_s=None, now=None):
        v = self.views[min(self.i, len(self.views) - 1)]
        self.i += 1
        return {
            "window_s": window_s or 5.0,
            "now": now,
            "replicas": self.n,
            "classes": {
                "gold": {
                    "completed": 10,
                    "shed": 0,
                    "slo_met": 0,
                    "slo_n": 0,
                    "slo_attainment": v.get("att"),
                }
            },
            "queue_depth_mean": v.get("q", 0.0) * self.n,
            "queue_depth_mean_per_replica": v.get("q", 0.0),
            "occupancy_mean": v.get("occ", 0.0),
            "pool_utilization_mean": v.get("pool", 0.0),
        }

    def add_replica(self):
        self.adds += 1
        self.n += 1

    def remove_replica(self):
        self.removes += 1
        self.n -= 1

    @property
    def num_replicas(self):
        return self.n


def _policy(**kw):
    kw.setdefault("target_class", "gold")
    kw.setdefault("queue_high", 4.0)
    kw.setdefault("queue_low", 0.5)
    kw.setdefault("occupancy_low", 0.5)
    kw.setdefault("breach_polls", 2)
    kw.setdefault("cooldown_out_s", 2.0)
    kw.setdefault("cooldown_in_s", 10.0)
    kw.setdefault("max_replicas", 8)
    return AutoscalePolicy(**kw)


OK = {"att": 1.0, "q": 1.0, "occ": 0.7}  # dead band: healthy, busy
BREACH = {"att": 0.5, "q": 1.0, "occ": 0.9}  # SLO broken
IDLE = {"att": 1.0, "q": 0.0, "occ": 0.1}  # scale-in band


class TestControllerLogic:
    def _drive(self, stub, policy, times, t0=0.0, dt=1.0):
        t = [t0]
        a = Autoscaler(stub, policy, clock=lambda: t[0])
        decs = []
        for _ in range(times):
            decs.append(a.poll())
            t[0] += dt
        return a, decs

    def test_blip_shorter_than_streak_does_not_resize(
        self, no_fault_plan
    ):
        """One bad window (chaos blip, restore cold start) between
        healthy polls: streak never reaches breach_polls => no
        resize."""
        stub = _StubRouter([OK, BREACH, OK, BREACH, OK, OK])
        a, decs = self._drive(stub, _policy(breach_polls=2), 6)
        assert stub.adds == 0 and stub.removes == 0
        assert all(d.action == "hold" for d in decs)
        assert any("streak" in d.reason for d in decs)

    def test_sustained_breach_scales_out_once_then_cooldown(
        self, no_fault_plan
    ):
        stub = _StubRouter([BREACH] * 6)
        a, decs = self._drive(
            stub, _policy(breach_polls=2, cooldown_out_s=10.0), 6
        )
        # poll 0 builds the streak, poll 1 acts, the rest sit in
        # cooldown (streak rebuilds but the cooldown gate holds)
        assert stub.adds == 1
        applied = [d for d in decs if d.outcome == "applied"]
        assert len(applied) == 1 and applied[0].action == "scale_out"
        assert applied[0].view["attainment"] == 0.5  # evidence logged
        assert any("cooldown" in d.reason for d in decs[2:])

    def test_oscillation_at_band_edge_is_bounded_by_cooldown(
        self, no_fault_plan
    ):
        """Load flapping across the out band edge every 2 polls: with
        breach_polls=1 every in-band poll could act, so the resize
        count over the horizon is bounded by elapsed/cooldown, not by
        the flap rate."""
        views = [BREACH if i % 2 == 0 else OK for i in range(40)]
        stub = _StubRouter(views)
        a, decs = self._drive(
            stub,
            _policy(breach_polls=1, cooldown_out_s=10.0, max_replicas=50),
            40,
        )  # 40 polls x 1s; flaps every poll, cooldown 10s
        assert stub.adds <= 4  # ceil(40 / 10)
        assert stub.adds >= 1

    def test_scale_in_requires_streak_and_respects_min(
        self, no_fault_plan
    ):
        stub = _StubRouter([IDLE] * 8, replicas=2)
        a, decs = self._drive(
            stub, _policy(breach_polls=3, min_replicas=1), 8
        )
        assert stub.removes == 1  # streak at poll 2, then min+cooldown
        stub2 = _StubRouter([IDLE] * 8, replicas=1)
        a2, decs2 = self._drive(
            stub2, _policy(breach_polls=3, min_replicas=1), 8
        )
        assert stub2.removes == 0
        assert any("min_replicas" in d.reason for d in decs2)

    def test_max_step_clamps_pressure(self, no_fault_plan):
        """Queue at 10x queue_high asks for 10 replicas; max_step caps
        the move, whatever the pressure reads."""
        stub = _StubRouter([{"att": 1.0, "q": 40.0, "occ": 1.0}] * 3)
        a, decs = self._drive(
            stub,
            _policy(breach_polls=1, max_step=2, cooldown_out_s=10.0),
            3,
        )
        applied = [d for d in decs if d.outcome == "applied"]
        assert applied and applied[0].amount == 2
        assert stub.adds == 2

    def test_max_replicas_bound(self, no_fault_plan):
        stub = _StubRouter([BREACH] * 5, replicas=8)
        a, decs = self._drive(
            stub, _policy(breach_polls=1, max_replicas=8), 5
        )
        assert stub.adds == 0
        assert all("max_replicas" in d.reason for d in decs)

    def test_force_overrides(self, no_fault_plan, monkeypatch):
        stub = _StubRouter([OK] * 4, replicas=2)
        t = [0.0]
        a = Autoscaler(stub, _policy(max_step=2), clock=lambda: t[0])
        monkeypatch.setenv("TDX_AUTOSCALE_FORCE", "out:5")
        d = a.poll()
        assert d.forced and d.action == "scale_out"
        assert d.amount == 2  # max_step still clamps a forced move
        monkeypatch.setenv("TDX_AUTOSCALE_FORCE", "replicas:2")
        d = a.poll()  # n=4 -> target 2: in by 2, within max_step
        assert d.action == "scale_in" and stub.n == 2
        monkeypatch.setenv("TDX_AUTOSCALE_FORCE", "hold")
        d = a.poll()
        assert d.action == "hold" and "forced" in d.reason
        monkeypatch.setenv("TDX_AUTOSCALE_FORCE", "garbage:x")
        with pytest.warns(RuntimeWarning, match="malformed"):
            d = a.poll()
        assert not d.forced  # malformed force falls back to the bands

    def test_decisions_are_replayable(self, no_fault_plan):
        """Same views + same clock => identical decision stream (the
        determinism claim: the log + TDX_AUTOSCALE_FORCE make any
        decision reproducible)."""
        views = [OK, BREACH, BREACH, BREACH, IDLE, IDLE, IDLE, IDLE]

        def drive():
            stub = _StubRouter(list(views), replicas=2)
            a, decs = self._drive(
                stub, _policy(breach_polls=2, cooldown_in_s=1.0), 8
            )
            return [
                (d.t, d.action, d.amount, d.reason, d.outcome)
                for d in decs
            ]

        assert drive() == drive()

    def test_snapshot_carries_decision_log(self, no_fault_plan):
        stub = _StubRouter([BREACH] * 3)
        a, _ = self._drive(stub, _policy(breach_polls=1), 3)
        snap = a.snapshot()
        assert snap["resizes"] == stub.adds
        assert snap["decisions"][0]["view"]["attainment"] == 0.5
        assert snap["policy"]["queue_high"] == 4.0


# ---------------------------------------------------------------------------
# chaos: the scale seams under injected faults
# ---------------------------------------------------------------------------


class TestScaleChaos:
    def test_transient_scale_out_fault_aborts_then_retries(self):
        model, params = _model()
        t = [0.0]
        r = _router(model, params, t, replicas=1)
        a = Autoscaler(
            r,
            _policy(breach_polls=1, cooldown_out_s=0.0),
            clock=lambda: t[0],
        )
        faults.install_plan(
            [{"point": "serve.scale_out", "action": "reset", "times": 1}],
            export_env=False,
        )
        try:
            # saturate the queue so the bands demand scale-out
            for i, p in enumerate(_prompts(*([5] * 10))):
                r.submit(p, 4, rid=f"r{i}", klass="bronze")
            r.step()
            t[0] += 1.0
            d1 = a.poll()
            assert d1.action == "scale_out"
            assert d1.outcome.startswith("aborted")
            assert r.num_replicas == 1  # consistent: nothing added
            t[0] += 1.0
            d2 = a.poll()  # fault exhausted: the retry lands
            assert d2.outcome == "applied"
            assert r.num_replicas == 2
        finally:
            faults.clear_plan()
        while r.step():
            t[0] += 0.5
        assert len(r.completions) == 10

    def test_transient_scale_in_fault_mid_flight_token_exact(self):
        """A transient fault at serve.scale_in fires BEFORE the drain:
        the victim keeps its slots, the gang keeps its size, and every
        in-flight request still finishes token-identically — then a
        clean retry actually removes it, also token-exact."""
        model, params = _model()
        prompts = _prompts(5, 6, 4, 7, 5, 6)
        budgets = [4, 5, 3, 4, 5, 3]
        from pytorch_distributed_example_tpu.serve import ServeEngine

        faults.clear_plan()
        ref_eng = ServeEngine(
            model, params, slots=2, min_bucket=4, classes=CLASSES
        )
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            ref_eng.submit(p, b, rid=f"r{i}", seed=i, klass="gold")
        ref = ref_eng.run(max_steps=800)

        t = [0.0]
        r = _router(model, params, t, replicas=2)
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            r.submit(
                p, b, rid=f"r{i}", seed=i, tenant=f"ten{i % 2}",
                klass="gold",
            )
        r.step()
        faults.install_plan(
            [{"point": "serve.scale_in", "action": "drop", "times": 1}],
            export_env=False,
        )
        try:
            with pytest.raises(faults.FaultTimeout):
                r.remove_replica()
            assert r.num_replicas == 2  # consistent size
            r.step()  # both replicas still serving
            removed = r.remove_replica()  # retry succeeds
            assert r.num_replicas == 1
            assert removed in (0, 1)
        finally:
            faults.clear_plan()
        while r.step():
            t[0] += 0.5
        assert set(r.completions) == set(ref)
        for rid in ref:
            assert r.completions[rid].tokens == ref[rid].tokens, rid

    def test_route_fault_leaves_nothing_half_routed(self):
        model, params = _model()
        t = [0.0]
        r = _router(model, params, t, replicas=2)
        p = _prompts(5)[0]
        faults.install_plan(
            [{"point": "router.route", "action": "reset", "times": 1}],
            export_env=False,
        )
        try:
            with pytest.raises(ConnectionResetError):
                r.submit(p, 3, rid="r0", tenant="acme", klass="gold")
            assert r.pending == 0  # nothing tracked, nothing enqueued
            rid = r.submit(p, 3, rid="r0", tenant="acme", klass="gold")
            assert rid == "r0"
        finally:
            faults.clear_plan()
        out = r.run(max_steps=300)
        assert "r0" in out


# ---------------------------------------------------------------------------
# load harness: trace determinism + a miniature end-to-end swing
# ---------------------------------------------------------------------------


class TestLoadHarness:
    def test_trace_replayable_by_seed(self):
        from tests._load_trace import make_trace

        a = make_trace(7, 20.0, 10.0, 100, 4, 64)
        b = make_trace(7, 20.0, 10.0, 100, 4, 64)
        assert len(a) == len(b) == 100
        for ea, eb in zip(a, b):
            assert ea["arrival"] == eb["arrival"]
            assert ea["tenant"] == eb["tenant"]
            assert ea["klass"] == eb["klass"]
            np.testing.assert_array_equal(ea["prompt"], eb["prompt"])
        c = make_trace(8, 20.0, 10.0, 100, 4, 64)
        assert any(
            ea["arrival"] != ec["arrival"] for ea, ec in zip(a, c)
        )
        arr = [e["arrival"] for e in a]
        assert arr == sorted(arr)
        assert 0.0 <= arr[0] and arr[-1] <= 20.0

    def test_trace_is_diurnal(self):
        """The rate curve actually swings: the mid-trace bin is several
        times the edge bins."""
        from tests._load_trace import make_trace

        ev = make_trace(0, 40.0, 10.0, 2000, 4, 64)
        bins, _ = np.histogram(
            [e["arrival"] for e in ev], bins=8, range=(0.0, 40.0)
        )
        assert max(bins[3], bins[4]) >= 4 * max(bins[0], bins[-1])

    def test_mini_swing_end_to_end(self, no_fault_plan):
        """A small diurnal swing as the regression guard: the
        controller rides a small burst out AND back in, everything
        completes, and chip-seconds beat an always-peak gang."""
        from tests._load_trace import make_trace, replay

        model, params = _model(max_seq_len=32)
        events = make_trace(3, 12.0, 8.0, 120, 3, 64)
        t = [0.0]
        r = _router(model, params, t, replicas=1)
        a = Autoscaler(
            r,
            _policy(
                breach_polls=1,
                queue_high=2.0,
                cooldown_out_s=0.5,
                cooldown_in_s=2.0,
                occupancy_low=0.6,
                max_replicas=3,
            ),
            clock=lambda: t[0],
            window_s=3.0,
        )
        # 0.04 virtual seconds a call where 0.05 used to stand: an engine
        # call hands out the PREVIOUS call's tokens, so a request of 3-7
        # tokens holds its slot seven calls where it held six, and the
        # same service time a request keeps the falling edge's queue
        # under the policy's `queue_low`
        replay(events, r, t, 0.04, autoscaler=a, poll_every_s=0.25)
        assert len(r.completions) == len(events)
        kinds = {e.kind for e in r.events}
        assert "add" in kinds and "remove" in kinds
        peak = max(e.replicas_after for e in r.events)
        assert peak >= 2
        # always-peak chip-seconds over the same span would be peak * T
        assert r.chip_seconds < peak * t[0]
