"""Multi-tenant SLO-aware admission + elastic serve drain/restore
(ISSUE 8 tentpole).

Queue layer: smooth-weighted-round-robin class scheduling, class-ordered
overload shedding (the worst class present is displaced, never FIFO
collapse), the requeue-vs-shed determinism fix (recovery requeues live
in an unbounded head lane that `put()`'s depth check never reads), and
the targeted `pop_specific` the engine's resource-acquisition loop
needs.

Engine layer: cross-class preemption (waiting gold evicts in-flight
bronze, which replays token-identically), class-aware pool-pressure
victims, per-class metrics + SLO attainment, and gold TTFT protection
under a bronze burst (fake clock, deterministic).

Elastic layer: CRC-sealed store checkpoints with newest-verified-
generation fallback, drain/restore token-identity — including restore
into a DIFFERENT TP degree (2-virtual-device mesh), the ISSUE's resize
claim — the exact fake-clock recovery-time metric, and the
`serve.drain` / `serve.restore` fault points.
"""

import numpy as np
import pytest

from pytorch_distributed_example_tpu import faults
from pytorch_distributed_example_tpu.serve.queue import (
    ClassSpec,
    QueueFullError,
    Request,
    RequestQueue,
)


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


def _req(klass="", L=4, budget=2, rid="", arrival=0.0):
    r = Request(
        prompt=np.ones(L, np.int32), max_new_tokens=budget, rid=rid,
        klass=klass,
    )
    r.arrival_time = arrival
    return r


CLASSES = {
    "gold": ClassSpec(priority=0, weight=6, ttft_slo_s=1.0),
    "silver": ClassSpec(priority=1, weight=3),
    "bronze": ClassSpec(priority=2, weight=1),
}


@pytest.fixture()
def no_fault_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


class TestClassQueue:
    def test_single_class_fifo_unchanged(self):
        """No classes configured: PR 4 FIFO semantics bit-for-bit."""
        q = RequestQueue(max_depth=2)
        a, b = _req(rid="a"), _req(rid="b")
        assert q.put(a) is None and q.put(b) is None
        with pytest.raises(QueueFullError):
            q.put(_req(rid="c"))
        assert q.pop().rid == "a"
        assert q.pop().rid == "b"

    def test_swrr_respects_weights(self):
        """Pop distribution over a long backlog tracks the class
        weights (6:3:1) and is FIFO within a class."""
        q = RequestQueue(classes=CLASSES)
        for i in range(30):
            q.put(_req("gold", rid=f"g{i}"))
            q.put(_req("silver", rid=f"s{i}"))
            q.put(_req("bronze", rid=f"b{i}"))
        first20 = [q.pop().rid for i in range(20)]
        counts = {
            k: sum(1 for r in first20 if r.startswith(k[0]))
            for k in CLASSES
        }
        assert counts["gold"] == 12 and counts["silver"] == 6
        assert counts["bronze"] == 2
        golds = [r for r in first20 if r.startswith("g")]
        assert golds == sorted(golds, key=lambda r: int(r[1:]))

    def test_peek_matches_pop_and_does_not_advance(self):
        q = RequestQueue(classes=CLASSES)
        for i in range(4):
            q.put(_req("gold", rid=f"g{i}"))
            q.put(_req("bronze", rid=f"b{i}"))
        for _ in range(6):
            assert q.peek() is q.peek()  # peek is stable
            head = q.peek()
            assert q.pop() is head  # and pop returns exactly it

    def test_shed_displaces_worst_class_not_fifo(self):
        """A gold put into a full queue displaces the NEWEST bronze —
        returned to the caller — instead of rejecting the gold."""
        q = RequestQueue(max_depth=3, classes=CLASSES)
        q.put(_req("bronze", rid="b0"))
        q.put(_req("bronze", rid="b1"))
        q.put(_req("silver", rid="s0"))
        victim = q.put(_req("gold", rid="g0"))
        assert victim.rid == "b1"  # newest of the worst class present
        # bronze into the full queue (now gold+silver+bronze): bronze is
        # still the worst present -> the incoming request is the victim
        with pytest.raises(QueueFullError):
            q.put(_req("bronze", rid="b2"))
        # equal-priority ties shed the INCOMING request (no churn)
        q2 = RequestQueue(max_depth=2, classes=CLASSES)
        q2.put(_req("silver", rid="s0"))
        q2.put(_req("silver", rid="s1"))
        with pytest.raises(QueueFullError):
            q2.put(_req("silver", rid="s2"))

    def test_requeue_vs_shed_ordering_deterministic(self):
        """REGRESSION (ISSUE 8 satellite): preemption-storm requeues
        must not change what `put()` sheds. Requeues land in an
        unbounded head lane invisible to the depth check, so both
        interleavings produce identical shed outcomes."""

        def run(requeue_first: bool):
            q = RequestQueue(max_depth=2)
            q.put(_req(rid="a"))
            q.put(_req(rid="b"))
            inflight = [_req(rid=f"i{k}") for k in range(3)]
            outcome = []
            if requeue_first:
                for r in inflight:  # preemption storm lands first
                    q.requeue_front(r)
            try:
                q.put(_req(rid="new"))
                outcome.append("accepted")
            except QueueFullError:
                outcome.append("shed")
            if not requeue_first:
                for r in inflight:  # storm lands after the put
                    q.requeue_front(r)
            return outcome, q.depth

        out_a, depth_a = run(requeue_first=True)
        out_b, depth_b = run(requeue_first=False)
        assert out_a == out_b == ["shed"]
        assert depth_a == depth_b == 5  # 2 bounded + 3 requeued

    def test_requeued_work_never_shed_and_pops_first(self):
        q = RequestQueue(max_depth=1, classes=CLASSES)
        q.put(_req("bronze", rid="b0"))
        inflight = _req("bronze", rid="i0")
        q.requeue_front(inflight)  # over depth: accepted (recovery path)
        assert q.depth == 2
        # a gold put sheds the SUBMITTED bronze, never the requeued one
        victim = q.put(_req("gold", rid="g0"))
        assert victim.rid == "b0"
        rids = [q.pop().rid for _ in range(2)]
        assert "i0" in rids and "g0" in rids

    def test_pop_specific_removes_target_and_charges_credits(self):
        q = RequestQueue(classes=CLASSES)
        g = _req("gold", rid="g0")
        q.put(g)
        q.put(_req("bronze", rid="b0"))
        assert q.pop_specific(g)
        assert not q.pop_specific(g)  # already gone
        assert q.pop().rid == "b0"
        assert q.pop() is None

    def test_unknown_class_rejected(self):
        q = RequestQueue(classes=CLASSES)
        with pytest.raises(ValueError, match="unknown class"):
            q.put(_req("platinum"))

    def test_request_state_roundtrip(self):
        r = _req("gold", L=3, budget=5, rid="x", arrival=2.5)
        r.tenant = "acme"
        r.seed = 17
        r.requeues = 2
        r2 = Request.from_state(r.to_state())
        assert r2.rid == "x" and r2.klass == "gold"
        assert r2.tenant == "acme" and r2.seed == 17
        assert r2.requeues == 2 and r2.arrival_time == 2.5
        np.testing.assert_array_equal(r2.prompt, r.prompt)
        assert r2.max_new_tokens == 5


class TestMultiTenantEngine:
    def _engine(self, model, params, **kw):
        from pytorch_distributed_example_tpu.serve import ServeEngine

        kw.setdefault("classes", CLASSES)
        kw.setdefault("slots", 2)
        kw.setdefault("min_bucket", 4)
        return ServeEngine(model, params, **kw)

    def test_gold_preempts_inflight_bronze(self, no_fault_plan):
        """All slots busy with bronze: a gold arrival evicts the
        youngest bronze (class_preempted metric), and the evicted
        bronze later completes token-identically to an uncontended
        run."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate

        model, params = _model()
        prompts = _prompts(5, 6, 4)
        t = [0.0]
        eng = self._engine(model, params, clock=lambda: t[0])
        b0 = eng.submit(prompts[0], 8, rid="b0", klass="bronze")
        t[0] = 0.5  # b1 is strictly younger: the deterministic victim
        b1 = eng.submit(prompts[1], 8, rid="b1", klass="bronze")
        t[0] = 1.0
        eng.step()  # both bronze admitted + prefilled
        assert eng.num_active == 2
        t[0] = 2.0
        g0 = eng.submit(prompts[2], 4, rid="g0", klass="gold")
        eng.step()
        assert eng.metrics.class_preempted == 1
        # the younger bronze (b1) gave up its slot; gold is in flight
        active = {
            eng._slot_req[s].rid
            for s in range(eng.cache.slots)
            if eng._slot_req[s] is not None
        }
        assert "g0" in active and "b1" not in active
        out = eng.run(max_steps=500)
        assert set(out) == {"b0", "b1", "g0"}
        for rid, p, m in (("b0", prompts[0], 8), ("b1", prompts[1], 8),
                          ("g0", prompts[2], 4)):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], m)
            )[0]
            np.testing.assert_array_equal(np.asarray(out[rid].tokens), ref)
        assert out["b1"].requeues >= 1  # the evictee replayed

    def test_no_futile_eviction_when_preemption_cannot_unblock(
        self, no_fault_plan
    ):
        """REGRESSION: a gold head whose block need exceeds free +
        every-bronze-victim's holdings must NOT evict anyone — evicting
        could not unblock it, so killing bronze work would be pure
        churn. (Here most of the pool is held by another GOLD request,
        which is never a victim.)"""
        model, params = _model(max_seq_len=48)
        prompts = _prompts(32, 4, 32)
        t = [0.0]
        eng = self._engine(
            model, params, slots=3, clock=lambda: t[0],
            block_size=4, pool_blocks=12,
        )
        eng.submit(prompts[0], 8, rid="g1", klass="gold")   # holds ~8 blocks
        t[0] = 0.5
        eng.submit(prompts[1], 6, rid="b1", klass="bronze")  # holds ~2
        eng.step()  # both prefilled and decoding
        assert eng.num_active == 2
        t[0] = 1.0
        eng.submit(prompts[2], 8, rid="g2", klass="gold")  # needs 8 blocks
        eng.step()
        # b1 must still be in flight and nothing was preempted
        active = {
            eng._slot_req[s].rid
            for s in range(eng.cache.slots)
            if eng._slot_req[s] is not None
        }
        assert "b1" in active
        assert eng.metrics.class_preempted == 0
        out = eng.run(max_steps=800)
        assert set(out) == {"g1", "b1", "g2"}

    def test_same_class_never_class_preempted(self, no_fault_plan):
        model, params = _model()
        prompts = _prompts(5, 6, 4)
        eng = self._engine(model, params)
        eng.submit(prompts[0], 6, rid="g0", klass="gold")
        eng.submit(prompts[1], 6, rid="g1", klass="gold")
        eng.step()
        eng.submit(prompts[2], 4, rid="g2", klass="gold")
        eng.run(max_steps=500)
        assert eng.metrics.class_preempted == 0

    def test_gold_ttft_protected_under_bronze_overload(self, no_fault_plan):
        """The acceptance shape at unit scale: a bronze burst saturates
        slots AND queue; gold arrivals mid-burst still see TTFT within
        ~1 step-time of an uncontended gold run (preemption + weighted
        admission), while bronze absorbs the sheds."""
        model, params = _model()
        prompts = _prompts(*([5] * 14))

        def run(classed):
            t = [0.0]
            eng = self._engine(
                model, params, clock=lambda: t[0],
                max_queue_depth=6,
                classes=CLASSES if classed else None,
            )
            gold_rids = []
            sheds = 0
            for i in range(10):  # bronze burst at t=0
                try:
                    eng.submit(
                        prompts[i], 6, rid=f"b{i}",
                        klass="bronze" if classed else "",
                    )
                except QueueFullError:
                    sheds += 1
            for k in range(10):
                t[0] += 1.0
                if k in (1, 3):  # gold arrivals mid-burst
                    rid = f"g{k}"
                    try:
                        eng.submit(
                            prompts[10 + len(gold_rids)], 4, rid=rid,
                            klass="gold" if classed else "",
                        )
                        gold_rids.append(rid)
                    except QueueFullError:
                        pass
                eng.step()
            while eng.step():
                t[0] += 1.0
            return eng, gold_rids

        eng, gold_rids = run(classed=True)
        assert gold_rids, "gold submissions must be admitted, not shed"
        gold_ttft = [eng.completions[r].ttft_s for r in gold_rids]
        # uncontended gold TTFT is ~1 fake-second (one step after
        # arrival); protected means a small constant, not the whole
        # bronze backlog drain (which takes > 6 fake-seconds)
        assert max(gold_ttft) <= 2.0, gold_ttft
        snap = eng.metrics.snapshot()
        assert snap["classes"]["bronze"]["shed"] >= 1
        assert snap["classes"]["gold"]["shed"] == 0
        assert snap["classes"]["gold"]["slo_attainment"] == 1.0
        # FIFO baseline: the same gold arrivals wait behind the burst
        fifo, fifo_gold = run(classed=False)
        if fifo_gold:  # bounded queue may shed them outright
            fifo_ttft = [fifo.completions[r].ttft_s for r in fifo_gold]
            assert min(fifo_ttft) > max(gold_ttft)

    def test_per_class_metrics_on_serve_snapshot(self, no_fault_plan):
        model, params = _model()
        prompts = _prompts(4, 4)
        eng = self._engine(model, params)
        eng.submit(prompts[0], 2, rid="g", klass="gold", tenant="acme")
        eng.submit(prompts[1], 2, rid="b", klass="bronze")
        out = eng.run(max_steps=200)
        assert out["g"].tenant == "acme" and out["g"].klass == "gold"
        snap = eng.metrics.snapshot()
        assert snap["classes"]["gold"]["completed"] == 1
        assert snap["classes"]["bronze"]["completed"] == 1
        assert snap["classes"]["gold"]["priority"] == 0
        assert snap["classes"]["gold"]["weight"] == 6
        assert "ttft_p99_ms" in snap["classes"]["bronze"]


class TestElasticServe:
    def test_store_checkpoint_crc_fallback(self):
        from pytorch_distributed_example_tpu.serve.elastic import (
            load_serve_state,
            save_serve_state,
        )
        from pytorch_distributed_example_tpu.store import HashStore

        faults.clear_plan()
        s = HashStore(timeout=1.0)
        assert load_serve_state(s) == (None, -1)  # fresh store: empty
        save_serve_state(s, 0, {"requests": [], "emitted": {},
                                "checkpoint_time": 1.0})
        save_serve_state(s, 1, {"requests": [], "emitted": {},
                                "checkpoint_time": 2.0})
        st, g = load_serve_state(s)
        assert g == 1 and st["checkpoint_time"] == 2.0
        # corrupt gen1 -> CRC detects, falls back to sealed gen0
        s.set("serve/ckpt/gen1", s.get("serve/ckpt/gen1")[:-4] + b"beef")
        with pytest.warns(RuntimeWarning, match="CRC"):
            st, g = load_serve_state(s)
        assert g == 0 and st["checkpoint_time"] == 1.0
        assert st["generation"] == 0

    def test_drain_restore_token_identity_and_recovery_metric(
        self, no_fault_plan
    ):
        """Kill-mid-traffic at unit scale (fake clock): drain a loaded
        engine, checkpoint through the store, restore into a FRESH
        engine, finish — outputs token-identical to an uninterrupted
        run, recovery time exactly the fake-clock gap, replay ledger
        counts the thrown-away tokens."""
        from pytorch_distributed_example_tpu.serve import ServeEngine
        from pytorch_distributed_example_tpu.serve.elastic import (
            load_serve_state,
            restore_into,
            save_serve_state,
        )
        from pytorch_distributed_example_tpu.store import HashStore

        model, params = _model()
        prompts = _prompts(5, 7, 4, 6, 8, 5)
        t = [0.0]

        def mk():
            return ServeEngine(
                model, params, slots=2, min_bucket=4,
                classes=CLASSES, clock=lambda: t[0],
            )

        def submit_all(eng):
            for i, p in enumerate(prompts):
                eng.submit(
                    p, 5, rid=f"r{i}", seed=i,
                    klass=["gold", "bronze", "silver"][i % 3],
                )

        ref = mk()
        submit_all(ref)
        ref_out = ref.run(max_steps=500)
        assert len(ref_out) == len(prompts)

        t[0] = 0.0
        e1 = mk()
        submit_all(e1)
        for _ in range(3):  # partway: some done, some mid-decode
            t[0] += 0.5
            e1.step()
        state = e1.drain()
        assert e1.num_active == 0  # drain requeued every slot
        mid_flight = sum(state["emitted"].values())
        store = HashStore(timeout=1.0)
        save_serve_state(store, 3, state)
        done_gen0 = dict(e1.completions)

        st, g = load_serve_state(store)
        assert g == 3
        t[0] += 4.0  # the gang was dark for 4 fake-seconds
        e2 = mk()
        n = restore_into(e2, st, generation=g)
        assert n == len(prompts) - len(done_gen0)
        while e2.step():
            t[0] += 0.5
        merged = dict(done_gen0)
        merged.update(e2.completions)
        assert set(merged) == set(ref_out)
        for rid in ref_out:
            assert merged[rid].tokens == ref_out[rid].tokens, rid
        rec = e2.metrics.snapshot()["recovery"]
        assert rec["restores"] == 1
        assert rec["requests_restored"] == n
        assert rec["tokens_replayed"] == mid_flight
        assert rec["restored_generation"] == 3
        # drain stamped t=1.5; the gang was dark until t=5.5, when the
        # first post-restore step prefills; the call after it, at t=6.0,
        # reads that prefill's token back and holds it -> 4.5
        assert rec["last_recovery_s"] == pytest.approx(4.5)

    def test_restore_into_different_tp_degree(self, no_fault_plan):
        """The resize claim: gen0 serves UNSHARDED, the re-formed gang
        restores at TP2 over a 2-virtual-device mesh — outputs stay
        token-identical (the snapshot carries no device state)."""
        import jax

        from pytorch_distributed_example_tpu.mesh import init_device_mesh
        from pytorch_distributed_example_tpu.serve import ServeEngine
        from pytorch_distributed_example_tpu.serve.elastic import (
            load_serve_state,
            restore_into,
            save_serve_state,
        )
        from pytorch_distributed_example_tpu.store import HashStore

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        mesh = init_device_mesh(("tp",), (2,), devices=jax.devices()[:2])
        model, params = _model()
        prompts = _prompts(5, 7, 4, 6)

        def submit_all(eng):
            for i, p in enumerate(prompts):
                eng.submit(p, 5, rid=f"r{i}", seed=i)

        ref = ServeEngine(model, params, slots=2, min_bucket=4)
        submit_all(ref)
        ref_out = ref.run(max_steps=500)

        e1 = ServeEngine(model, params, slots=2, min_bucket=4)
        submit_all(e1)
        for _ in range(2):
            e1.step()
        store = HashStore(timeout=1.0)
        save_serve_state(store, 0, e1.drain())

        st, g = load_serve_state(store)
        e2 = ServeEngine(model, params, slots=2, min_bucket=4, mesh=mesh)
        restore_into(e2, st, generation=g)
        e2.run(max_steps=500)
        merged = dict(e1.completions)
        merged.update(e2.completions)
        assert set(merged) == set(ref_out)
        for rid in ref_out:
            assert merged[rid].tokens == ref_out[rid].tokens, rid

    def test_restored_backlog_stays_bounded_and_sheddable(
        self, no_fault_plan
    ):
        """REGRESSION: the never-admitted submitted backlog restores
        into the BOUNDED tails, not the exempt head lanes — so after a
        restore, (a) the depth bound still sees it and (b) a gold
        submit can still displace restored bronze (class shed survives
        the restart)."""
        from pytorch_distributed_example_tpu.serve import ServeEngine
        from pytorch_distributed_example_tpu.serve.elastic import (
            restore_into,
        )

        model, params = _model()
        prompts = _prompts(4, 4, 4, 4, 5)
        eng = ServeEngine(
            model, params, slots=1, min_bucket=4,
            classes=CLASSES, max_queue_depth=3,
        )
        # slot busy + 3 bronze queued (tail at the bound)
        eng.submit(prompts[0], 6, rid="b0", klass="bronze")
        eng.step()  # b0 occupies the slot; the tail is empty again
        for i in range(1, 4):
            eng.submit(prompts[i], 6, rid=f"b{i}", klass="bronze")
        state = eng.drain()
        assert len(state["queued"]) == 3  # never-admitted tail backlog

        e2 = ServeEngine(
            model, params, slots=1, min_bucket=4,
            classes=CLASSES, max_queue_depth=3,
        )
        restore_into(e2, state, generation=0)
        # (a) bound intact: a new bronze submit is shed, not accepted
        with pytest.raises(QueueFullError):
            e2.submit(prompts[4], 2, rid="b-new", klass="bronze")
        # (b) class shed intact: gold displaces a RESTORED bronze
        e2.submit(prompts[4], 2, rid="g0", klass="gold")
        assert any(r.startswith("b") for r in e2.shed_requests)
        out = e2.run(max_steps=600)
        assert "g0" in out

    def test_empty_restore_records_zero_recovery(self, no_fault_plan):
        """REGRESSION: restoring an EMPTY snapshot must not arm a
        recovery window that later unrelated traffic would close with
        a bogus hours-long last_recovery_s."""
        from pytorch_distributed_example_tpu.serve import ServeEngine
        from pytorch_distributed_example_tpu.serve.elastic import (
            restore_into,
        )

        model, params = _model()
        t = [0.0]
        idle = ServeEngine(
            model, params, slots=1, min_bucket=4, clock=lambda: t[0]
        )
        state = idle.drain()  # nothing queued, nothing in flight
        e2 = ServeEngine(
            model, params, slots=1, min_bucket=4, clock=lambda: t[0]
        )
        assert restore_into(e2, state, generation=2) == 0
        t[0] = 3600.0  # a long idle gap before fresh traffic
        e2.submit(_prompts(4)[0], 2, rid="r0")
        e2.run(max_steps=200)
        rec = e2.metrics.snapshot()["recovery"]
        assert rec["restores"] == 1
        assert rec["last_recovery_s"] == 0.0  # not the idle gap
        assert rec["restored_generation"] == 2

    def test_serve_drain_fault_leaves_engine_intact(self, no_fault_plan):
        """A transient fault at serve.drain aborts the snapshot with
        nothing requeued — the engine just keeps serving."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        eng = ServeEngine(model, params, slots=2, min_bucket=4)
        for i, p in enumerate(_prompts(5, 6)):
            eng.submit(p, 4, rid=f"r{i}")
        eng.step()
        active_before = eng.num_active
        assert active_before > 0
        faults.install_plan(
            [{"point": "serve.drain", "action": "reset"}],
            export_env=False,
        )
        with pytest.raises(ConnectionResetError):
            eng.drain()
        faults.clear_plan()
        assert eng.num_active == active_before  # untouched
        out = eng.run(max_steps=300)
        assert len(out) == 2

    def test_serve_restore_fault_point_fires(self):
        from pytorch_distributed_example_tpu.serve.elastic import (
            load_serve_state,
        )
        from pytorch_distributed_example_tpu.store import HashStore

        faults.install_plan(
            [{"point": "serve.restore", "action": "drop"}],
            export_env=False,
        )
        try:
            with pytest.raises(faults.FaultTimeout):
                load_serve_state(HashStore(timeout=1.0))
        finally:
            faults.clear_plan()

    def test_gc_keeps_fallback_chain_under_corrupt_newest(self):
        """Snapshot-generation GC must stay anchored on the newest
        VERIFIED generation: after reclaiming, a corrupt newest blob
        still falls back onto a sealed predecessor GC was forbidden to
        touch."""
        from pytorch_distributed_example_tpu.serve.elastic import (
            gc_serve_state,
            load_serve_state,
            save_serve_state,
        )
        from pytorch_distributed_example_tpu.store import HashStore

        faults.clear_plan()
        s = HashStore(timeout=1.0)
        for g in range(4):
            save_serve_state(
                s, g, {"requests": [], "emitted": {},
                       "checkpoint_time": float(g)}
            )
        st, g = load_serve_state(s)
        assert g == 3
        # verified=3, keep=2 -> generations {1, 2, 3} stay; only gen0 goes
        assert gc_serve_state(s, g, keep=2) == 1
        assert not s.check(["serve/ckpt/gen0"])
        for kept in (1, 2, 3):
            assert s.check([f"serve/ckpt/gen{kept}"])
        # idempotent: nothing below the floor remains
        assert gc_serve_state(s, g, keep=2) == 0
        # corrupt the newest AFTER the reclaim — the fallback chain GC
        # preserved still restores gen2
        s.set("serve/ckpt/gen3", b"not a sealed blob")
        with pytest.warns(RuntimeWarning, match="CRC"):
            st, g = load_serve_state(s)
        assert g == 2 and st["checkpoint_time"] == 2.0
        # and GC anchored on THAT verified gen keeps its own margin
        assert gc_serve_state(s, g, keep=2) == 0
        assert s.check(["serve/ckpt/gen1"])
        # degenerate inputs are no-ops, never raises
        assert gc_serve_state(s, -1) == 0
        assert gc_serve_state(s, 2, keep=-1) == 0

    def test_drain_signalling_helpers(self):
        from pytorch_distributed_example_tpu.serve.elastic import (
            drain_requested,
            signal_drain,
        )
        from pytorch_distributed_example_tpu.store import HashStore

        faults.clear_plan()
        s = HashStore(timeout=1.0)
        assert not drain_requested(s, 0)
        signal_drain(s, 0)
        assert drain_requested(s, 0)
        assert not drain_requested(s, 1)  # generation-scoped
