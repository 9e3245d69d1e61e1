"""Serve subsystem tests (`serve/`): slot KV cache lifecycle,
slot-prefill parity vs the whole-batch decode path, continuous-batching
engine correctness (token-exact greedy parity vs `generate()`,
mid-stream retire+backfill determinism), fake-clock TTFT/TPOT
accounting, chaos requeue (serve.* fault points), and the /serve debug
HTTP route.

The load-bearing acceptance check lives in TestEngineParity: engine
outputs must be TOKEN-EXACT vs the non-batched `generate()` path for
identical prompts/seeds (greedy), across staggered admissions, slot
retirement, and backfill — the per-slot positions/masks and padded
prefill have to line up exactly for that to hold.
"""

import json
import urllib.request

import numpy as np
import pytest

from pytorch_distributed_example_tpu import faults


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


@pytest.fixture()
def no_fault_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


class TestBucketing:
    def test_bucket_lengths_and_lookup(self):
        from pytorch_distributed_example_tpu.serve import (
            bucket_for,
            bucket_lengths,
        )

        bs = bucket_lengths(48, min_bucket=8)
        assert bs == (8, 16, 32, 48)
        assert bucket_for(5, bs) == 8
        assert bucket_for(16, bs) == 16
        assert bucket_for(33, bs) == 48
        with pytest.raises(ValueError, match="exceeds"):
            bucket_for(49, bs)

    def test_power_of_two_max(self):
        from pytorch_distributed_example_tpu.serve import bucket_lengths

        assert bucket_lengths(64, min_bucket=16) == (16, 32, 64)


class TestSlotCache:
    def test_allocate_free_reset(self):
        from pytorch_distributed_example_tpu.serve import SlotKVCache

        model, _ = _model()
        c = SlotKVCache(model, 3)
        s0, s1, s2 = c.allocate(), c.allocate(), c.allocate()
        assert sorted([s0, s1, s2]) == [0, 1, 2]
        assert c.allocate() is None  # full
        assert c.occupancy == 1.0
        c.free(s1)
        assert c.allocate() == s1  # recycled
        c.free(s2)
        with pytest.raises(ValueError, match="not allocated"):
            c.free(s2)  # double free
        c.reset()
        assert c.active_slots == [] and c.occupancy == 0.0
        assert (c.lengths == 0).all()

    def test_write_prefill_validates(self):
        from pytorch_distributed_example_tpu.serve import SlotKVCache
        from pytorch_distributed_example_tpu.models import init_cache

        model, _ = _model()
        c = SlotKVCache(model, 2)
        pre = init_cache(model, 1)
        with pytest.raises(ValueError, match="not allocated"):
            c.write_prefill(0, pre, 4)
        s = c.allocate()
        with pytest.raises(ValueError, match="outside"):
            c.write_prefill(s, pre, 0)
        with pytest.raises(ValueError, match="outside"):
            c.write_prefill(s, pre, model.cfg.max_seq_len + 1)


class TestSlotPrefillParity:
    def test_prefill_into_slot_matches_whole_batch_prefill(self):
        """Bucket-padded prefill-into-slot == the unpadded whole-batch
        decode prefill: first-token logits AND the cache's valid region
        are identical; other slots stay untouched."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.serve import SlotKVCache
        from pytorch_distributed_example_tpu.serve.decode import (
            slot_programs,
        )

        model, params = _model()
        p = params["params"]
        (prompt,) = _prompts(5)
        L = len(prompt)

        prefill, _write, _step = slot_programs(model, 0.0, None)
        padded = np.zeros((1, 8), np.int32)  # bucket 8 > L=5
        padded[0, :L] = prompt
        pre_cache, first_logits, first, _key = prefill(
            p, jnp.asarray(padded), L, 0
        )

        # oracle: the existing scalar-index prefill on the UNPADDED prompt
        import jax

        oracle_cache = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32), decode=True
        )["cache"]
        logits, v2 = model.apply(
            {"params": p, "cache": oracle_cache},
            jnp.asarray(prompt)[None],
            decode=True,
            mutable=["cache"],
        )
        np.testing.assert_allclose(
            np.asarray(first_logits), np.asarray(logits[0, -1]),
            rtol=1e-6, atol=1e-6,
        )
        assert int(first) == int(np.argmax(np.asarray(logits[0, -1])))
        for layer in pre_cache:
            for kv in ("k", "v"):
                np.testing.assert_allclose(
                    np.asarray(pre_cache[layer]["attn"][kv][:, :L]),
                    np.asarray(v2["cache"][layer]["attn"][kv][:, :L]),
                    rtol=1e-6, atol=1e-6,
                )

        # landing it in slot 1 of 3 touches ONLY slot 1
        cache = SlotKVCache(model, 3)
        cache.allocate(), cache.allocate()  # slots 0, 1
        cache.write_prefill(1, pre_cache, L)
        assert cache.lengths.tolist() == [0, L, 0]
        for layer in cache.tree:
            got = np.asarray(cache.tree[layer]["attn"]["k"])
            want = np.asarray(pre_cache[layer]["attn"]["k"])
            np.testing.assert_array_equal(got[1], want[0])
            assert (got[0] == 0).all() and (got[2] == 0).all()


class TestEngineParity:
    def test_greedy_token_exact_vs_generate(self, no_fault_plan):
        """ACCEPTANCE: continuous-batching outputs are token-exact vs
        the non-batched generate() path — mixed prompt lengths and
        token budgets over 2 slots force mid-stream retirement AND
        backfill while other requests are in flight."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(5, 7, 3, 6, 4)
        budgets = [6, 4, 9, 5, 7]
        eng = ServeEngine(model, params, slots=2, min_bucket=4)
        rids = [
            eng.submit(p, m) for p, m in zip(prompts, budgets)
        ]
        out = eng.run(max_steps=300)
        assert eng.metrics.completed == len(prompts)
        for p, m, r in zip(prompts, budgets, rids):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], m)
            )[0]
            np.testing.assert_array_equal(np.asarray(out[r].tokens), ref)

    def test_backfill_happens_mid_stream(self, no_fault_plan):
        """With 2 slots and 4 requests, later requests must be admitted
        BEFORE earlier long ones finish (continuous batching, not
        run-to-completion batches)."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(4, 4, 4, 4)
        eng = ServeEngine(model, params, slots=2, min_bucket=4)
        long_rid = eng.submit(prompts[0], 12)
        eng.submit(prompts[1], 3)
        eng.submit(prompts[2], 3)
        eng.submit(prompts[3], 3)
        seen_backfill = False
        while eng.step():
            # a short request admitted while the long one is active
            active = {
                req.rid
                for req in eng._slot_req  # noqa: SLF001 — test introspection
                if req is not None
            }
            if long_rid in active and len(active) == 2:
                seen_backfill = True
        assert seen_backfill
        assert eng.metrics.completed == 4

    def test_eos_retires_slot_early(self, no_fault_plan):
        """Pick an eos id FROM a free engine run (guaranteed to fire):
        the request retires at eos with fewer tokens than its budget,
        matching generate()'s frozen row up to the eos position."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        # re-baselined for the installed PRNG stream (PR 21 dropped the
        # legacy jax_threefry_partitionable=False pin): under it the
        # random-init model's greedy run from the old length-4 prompt
        # repeats ONE token, so "the token at step 2" was also the token
        # at step 0. The eos is now the first token whose first
        # occurrence is at step >= 2, found from the free run itself.
        (prompt,) = _prompts(3)
        free = ServeEngine(model, params, slots=1, min_bucket=4)
        rid = free.submit(prompt, 12)
        toks = free.run(max_steps=100)[rid].tokens
        k = next(i for i in range(2, 11) if toks[i] not in toks[:i])
        eos = toks[k]  # first emitted at step k

        eng = ServeEngine(model, params, slots=1, eos_id=eos, min_bucket=4)
        rid2 = eng.submit(prompt, 12)
        comp = eng.run(max_steps=100)[rid2]
        assert comp.finish_reason == "eos"
        assert comp.tokens[-1] == eos
        assert len(comp.tokens) == k + 1  # retired early, budget was 12
        ref = np.asarray(
            generate(
                model, params, jnp.asarray(prompt)[None], 12, eos_id=eos
            )
        )[0]
        np.testing.assert_array_equal(comp.tokens, ref[: len(comp.tokens)])

    def test_sampling_reproducible_per_seed(self, no_fault_plan):
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(5, 6)

        def run_once():
            eng = ServeEngine(
                model, params, slots=2, temperature=0.8, top_k=8,
                min_bucket=4,
            )
            rids = [
                eng.submit(p, 5, seed=7 + i)
                for i, p in enumerate(prompts)
            ]
            out = eng.run(max_steps=100)
            return [out[r].tokens for r in rids]

        a, b = run_once(), run_once()
        assert a == b
        # a different seed produces a different stream
        eng = ServeEngine(
            model, params, slots=2, temperature=0.8, top_k=8, min_bucket=4
        )
        rid = eng.submit(prompts[0], 5, seed=99)
        c = eng.run(max_steps=100)[rid].tokens
        assert c != a[0]

    def test_submit_validation(self, no_fault_plan):
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        eng = ServeEngine(model, params, slots=1, min_bucket=4)
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros((0,), np.int32), 4)
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit(np.zeros((30,), np.int32), 4)  # 30 + 4 > 32
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.zeros((4,), np.int32), 0)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestMetricsAccounting:
    def test_ttft_tpot_with_fake_clock(self, no_fault_plan):
        """Deterministic latency accounting: a scripted clock pins
        arrival -> first-token -> completion timestamps exactly."""
        from pytorch_distributed_example_tpu.serve import (
            ServeEngine,
            ServeMetrics,
        )

        model, params = _model()
        (prompt,) = _prompts(4)
        fc = _FakeClock()
        eng = ServeEngine(
            model, params, slots=1, min_bucket=4, clock=fc,
            metrics=ServeMetrics(clock=fc, slots=1),
        )
        fc.t = 1.0
        rid = eng.submit(prompt, 3)
        fc.t = 5.0
        eng.step()  # admit (first token at t=5) + decode (token 2 at t=5)
        fc.t = 7.0
        eng.step()  # token 3 at t=7 -> completes (budget 3)
        comp = eng.completions[rid]
        assert comp.ttft_s == pytest.approx(4.0)  # 5 - 1
        assert comp.e2e_s == pytest.approx(6.0)  # 7 - 1
        assert comp.tpot_s == pytest.approx(1.0)  # (7 - 5) / (3 - 1)
        snap = eng.metrics.snapshot()
        assert snap["completed"] == 1
        assert snap["latency"]["ttft"]["p50_ms"] == pytest.approx(4000.0)
        assert snap["latency"]["tpot"]["p50_ms"] == pytest.approx(1000.0)
        assert snap["latency"]["e2e"]["p99_ms"] == pytest.approx(6000.0)
        assert snap["tokens_completed"] == 3
        # goodput window: first submit (1.0) -> last complete (7.0)
        assert snap["goodput_tokens_per_sec"] == pytest.approx(0.5)

    def test_queue_depth_and_occupancy_gauges(self, no_fault_plan):
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(4, 4, 4)
        eng = ServeEngine(model, params, slots=1, min_bucket=4)
        for p in prompts:
            eng.submit(p, 2)
        assert eng.queue.depth == 3
        eng.step()
        snap = eng.metrics.snapshot()
        assert snap["slots"] == 1
        assert snap["queue_depth"] == 2  # one admitted, two waiting
        assert snap["mean_occupancy"] == 1.0
        eng.run(max_steps=100)
        assert eng.metrics.snapshot()["queue_depth"] == 0

    def test_percentile_helper(self):
        from pytorch_distributed_example_tpu.serve.metrics import percentile

        assert percentile([], 99) == 0.0
        assert percentile([3.0], 50) == 3.0
        xs = [float(i) for i in range(1, 101)]
        assert percentile(xs, 50) == pytest.approx(50.5)
        assert percentile(xs, 99) == pytest.approx(99.01)


class TestServeChaos:
    def test_step_fault_requeues_and_replays_exactly(self, no_fault_plan):
        """CHAOS (acceptance): a mid-stream kill at serve.step drains
        every in-flight request back to the queue; the engine re-admits
        and replays them from scratch, and greedy outputs are
        token-identical to the fault-free run."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(5, 7, 3, 6)
        budgets = [6, 4, 9, 5]

        clean = ServeEngine(model, params, slots=2, min_bucket=4)
        crids = [clean.submit(p, m) for p, m in zip(prompts, budgets)]
        want = clean.run(max_steps=300)

        faults.install_plan(
            [{"point": "serve.step", "action": "reset", "after": 3}],
            export_env=False,
        )
        eng = ServeEngine(model, params, slots=2, min_bucket=4)
        rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
        out = eng.run(max_steps=400)
        assert eng.metrics.requeued >= 2  # both in-flight slots drained
        assert eng.metrics.completed == len(prompts)
        for cr, r in zip(crids, rids):
            assert want[cr].tokens == out[r].tokens
        # the replayed requests carry their requeue count
        assert any(out[r].requeues > 0 for r in rids)

    def test_admit_fault_retries_from_queue_head(self, no_fault_plan):
        """A dropped admission (serve.admit) leaves the request at the
        queue HEAD; the next step retries it — order preserved, output
        unchanged."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(5, 6)

        clean = ServeEngine(model, params, slots=1, min_bucket=4)
        crids = [clean.submit(p, 4) for p in prompts]
        want = clean.run(max_steps=100)

        faults.install_plan(
            [{"point": "serve.admit", "action": "drop", "after": 2}],
            export_env=False,
        )
        eng = ServeEngine(model, params, slots=1, min_bucket=4)
        rids = [eng.submit(p, 4) for p in prompts]
        out = eng.run(max_steps=200)
        assert eng.metrics.requeued == 1
        for cr, r in zip(crids, rids):
            assert want[cr].tokens == out[r].tokens
        # FIFO preserved: first submitted completed first
        assert out[rids[0]].e2e_s <= out[rids[1]].e2e_s

    def test_requeue_inflight_drains_slots(self, no_fault_plan):
        """Direct drain API: requeue_inflight() frees every slot and
        re-queues the requests; a subsequent run completes them all."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(5, 6)
        eng = ServeEngine(model, params, slots=2, min_bucket=4)
        rids = [eng.submit(p, 8) for p in prompts]
        eng.step()
        assert eng.num_active == 2
        n = eng.requeue_inflight()
        assert n == 2 and eng.num_active == 0 and eng.queue.depth == 2
        out = eng.run(max_steps=200)
        assert all(r in out for r in rids)

    def test_requeue_inflight_restores_arrival_order(self, no_fault_plan):
        """A drain after backfill has recycled slots must requeue by
        ARRIVAL time, not slot index: with slots=2, A finishes and C
        backfills slot 0 while B (older than C) still runs in slot 1 —
        the drained queue must read [B, C]."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        pa, pb, pc = _prompts(4, 5, 6)
        eng = ServeEngine(model, params, slots=2, min_bucket=4)
        eng.submit(pa, 1, rid="A")  # retires at admission (budget 1)
        rb = eng.submit(pb, 12, rid="B")
        rc = eng.submit(pc, 12, rid="C")
        eng.step()  # A done, B in slot 1, C backfilled into slot 0
        assert "A" in eng.completions and eng.num_active == 2
        assert eng.requeue_inflight() == 2
        drained = [eng.queue.pop().rid for _ in range(2)]
        assert drained == [rb, rc]


class TestServeHttp:
    def test_serve_route_exposes_metrics(self, no_fault_plan):
        from pytorch_distributed_example_tpu.serve import ServeEngine
        from pytorch_distributed_example_tpu.utils.debug_http import (
            DebugServer,
        )

        model, params = _model()
        (prompt,) = _prompts(4)
        eng = ServeEngine(model, params, slots=1, min_bucket=4)
        rid = eng.submit(prompt, 3)
        eng.run(max_steps=100)
        assert rid in eng.completions

        srv = DebugServer()
        try:
            srv.register_serve_metrics("engine", eng.metrics)
            with urllib.request.urlopen(srv.url + "/serve") as r:
                doc = json.loads(r.read())
            assert doc["engine"]["completed"] == 1
            assert doc["engine"]["tokens_completed"] == 3
            assert "goodput_tokens_per_sec" in doc["engine"]
            with urllib.request.urlopen(srv.url + "/") as r:
                assert "/serve" in json.loads(r.read())["routes"]
        finally:
            srv.shutdown()
