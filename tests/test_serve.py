"""Serve subsystem tests (`serve/`): the paged cache's slot lifecycle,
the two cache modes, paged prefill isolation, continuous-batching
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


class TestPagedSlotLifecycle:
    def test_full_double_free_reset(self):
        """The slot side of the paged cache (the block side is
        `tests/test_serve_paged.py::TestPagedPoolLifecycle`): a full
        cache grants nothing, a freed slot is recycled, a double free is
        refused, and reset returns every slot and block."""
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model()
        c = PagedKVCache(model, slots=3, num_blocks=12, block_size=4)
        s0, s1, s2 = c.allocate(), c.allocate(), c.allocate()
        assert sorted([s0, s1, s2]) == [0, 1, 2]
        assert c.allocate() is None  # full
        assert c.occupancy == 1.0
        c.free(s1)
        assert c.allocate() == s1  # recycled
        c.free(s2)
        with pytest.raises(ValueError, match="not allocated"):
            c.free(s2)  # double free
        assert c.ensure_blocks(s0, 9)
        c.reset()
        assert c.active_slots == [] and c.occupancy == 0.0
        assert (c.lengths == 0).all()
        assert c.live_blocks == 0 and c.free_blocks == 12
        assert (c.block_tables == c.invalid_block).all()


class TestCacheModes:
    @pytest.mark.parametrize(
        "given, match",
        [
            pytest.param(
                "positions", "block_tables", id="positions_without_tables"
            ),
            pytest.param(
                "block_tables", "requires positions",
                id="tables_without_positions",
            ),
        ],
    )
    def test_refused(self, given, match):
        """Cached attention has two modes: the scalar-index dense cache
        (neither argument) and the paged pool (both). One argument
        without the other is refused by name."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import init_cache

        model, params = _model()
        kw = {
            "positions": jnp.zeros((2,), jnp.int32),
            "block_tables": jnp.zeros((2, 8), jnp.int32),
        }
        with pytest.raises(ValueError, match=match):
            model.apply(
                {"params": params["params"], "cache": init_cache(model, 2)},
                jnp.zeros((2, 1), jnp.int32),
                decode=True,
                mutable=["cache"],
                **{given: kw[given]},
            )


class TestPagedPrefillIsolation:
    @pytest.mark.parametrize(
        "quantized", [False, True], ids=["plain", "int8"]
    )
    def test_only_the_row_s_blocks_change(self, quantized):
        """A 5-token prompt in an 8-token chunk into slot 1 of 3: every
        pool block outside slot 1's table (K, V and, for the int8 pool,
        the scale planes) is bit-unchanged, another live row's blocks
        among them; for the plain pool the first-token logits are those
        of the scalar-index prefill on the UNPADDED prompt."""
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import init_cache
        from pytorch_distributed_example_tpu.serve import (
            PagedKVCache,
            paged_programs,
        )

        model, params = _model()
        p = params["params"]
        (prompt,) = _prompts(5)
        L, C = len(prompt), 8

        cache = PagedKVCache(
            model, slots=3, num_blocks=12, block_size=4, quantized=quantized
        )
        assert (cache.allocate(), cache.allocate()) == (0, 1)
        assert cache.ensure_blocks(0, 7)  # a live neighbour's blocks
        assert cache.ensure_blocks(1, L - 1)
        # a pool of recognisable values, so an unchanged block shows
        cache.tree = jax.tree_util.tree_map(
            lambda x: (jnp.arange(x.size).reshape(x.shape) % 7 + 1).astype(
                x.dtype
            ),
            cache.tree,
        )
        before = jax.tree_util.tree_map(np.array, cache.tree)  # donated below

        prefill_chunk, first_token, _attach, _step = paged_programs(
            model, 0.0, None
        )
        padded = np.zeros((1, C), np.int32)
        padded[0, :L] = prompt
        tree, logits = prefill_chunk(
            p, cache.tree, jnp.asarray(padded),
            cache.tables(slice(1, 2)), 0,
        )

        mine = cache.slot_blocks(1)
        others = [b for b in range(12) if b not in mine]
        leaves = set()
        for layer in tree:
            for name, leaf in tree[layer]["attn"].items():
                got, was = np.asarray(leaf), before[layer]["attn"][name]
                np.testing.assert_array_equal(got[others], was[others])
                assert (got[mine] != was[mine]).any(), (layer, name)
                leaves.add(name)
        want = {"k", "v"} | ({"k_scale", "v_scale"} if quantized else set())
        assert leaves == want
        if quantized:
            return

        # oracle: the scalar-index prefill on the UNPADDED prompt
        ref, _ = model.apply(
            {"params": p, "cache": init_cache(model, 1)},
            jnp.asarray(prompt)[None],
            decode=True,
            mutable=["cache"],
        )
        np.testing.assert_allclose(
            np.asarray(logits[L - 1]), np.asarray(ref[0, -1]),
            rtol=1e-6, atol=1e-6,
        )
        first, _key = first_token(logits, L - 1, 0)
        assert int(first) == int(np.argmax(np.asarray(ref[0, -1])))


def test_public_surface():
    """Every public name of the cache and program modules resolves and
    is re-exported by `serve`, and the two modules export exactly the
    paged cache and its programs: one serving layout, no second."""
    from pytorch_distributed_example_tpu import serve
    from pytorch_distributed_example_tpu.serve import cache, decode

    for mod in (cache, decode):
        for name in mod.__all__:
            assert getattr(serve, name) is getattr(mod, name), name
    assert sorted(cache.__all__) == ["PagedKVCache", "init_paged_cache"]
    assert sorted(decode.__all__) == [
        "carry_key", "kernel_layers", "layer_paths", "paged_programs",
        "sync_slot_lanes",
    ]
    assert [n for n in vars(serve) if n.endswith("KVCache")] == ["PagedKVCache"]


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
        fc.t = 3.0
        eng.step()  # admit, prefill and decode step 1 dispatched: nothing read
        fc.t = 5.0
        eng.step()  # decode step 2 dispatched; first token, token 2 read at t=5
        assert rid not in eng.completions
        fc.t = 7.0
        assert not eng.step()  # token 3 read at t=7 -> completes (budget 3)
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
        eng.step()  # A and B prefilled; A's one token is still on the device
        assert "A" not in eng.completions and eng.num_active == 2
        eng.step()  # A's token read: A done, its slot free at the call's end
        eng.step()  # B in slot 1, C backfilled into slot 0
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
