"""Paged KV cache + chunked prefill + tensor-parallel decode tests
(ISSUE 6): block-pool lifecycle (allocate/free/reuse after retire,
fragmentation, all-or-nothing out-of-blocks backpressure), token-exact
greedy parity vs `generate()` with the paged cache — chunked prefill
and pool-pressure preemption included — bounded-admission shed,
long-prompt-burst TTFT bounding under a deterministic token-cost
clock, cache-pool metrics (the >= 4x dense-reduction claim, pinned),
and TP decode on a CPU mesh (2 virtual devices tier-1; wider mesh
marked slow).

The engine under test here IS the production engine — `ServeEngine`
runs the paged pool unconditionally — so these tests complement
`tests/test_serve.py`'s PR 4 contract (which now also exercises the
paged path) with the paged-only surfaces.
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


def _tp_mesh(n):
    import jax

    from pytorch_distributed_example_tpu.mesh import init_device_mesh

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    return init_device_mesh(("tp",), (n,), devices=jax.devices()[:n])


@pytest.fixture()
def no_fault_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestPagedPoolLifecycle:
    def test_allocate_write_free_reuse(self):
        """Blocks are allocated on write (not at slot grant), freed at
        retire, and reused FIFO by later requests."""
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model()
        c = PagedKVCache(model, slots=2, num_blocks=8, block_size=4)
        s = c.allocate()
        assert c.slot_blocks(s) == []  # slot grant costs no blocks
        assert c.free_blocks == 8
        assert c.ensure_blocks(s, 0)  # first token -> first block
        assert c.slot_blocks(s) == [0]
        assert c.ensure_blocks(s, 3)  # same block, no growth
        assert c.slot_blocks(s) == [0]
        assert c.ensure_blocks(s, 9)  # positions 4..9 -> blocks 1, 2
        assert c.slot_blocks(s) == [0, 1, 2]
        assert c.block_tables[s, :3].tolist() == [0, 1, 2]
        assert c.live_blocks == 3 and c.free_blocks == 5

        assert c.free(s) == 3  # retire returns every block
        assert c.free_blocks == 8 and c.live_blocks == 0
        assert (c.block_tables[s] == c.invalid_block).all()

        s2 = c.allocate()
        assert c.ensure_blocks(s2, 4)
        # FIFO reuse: the pool hands back the oldest-freed ids first
        assert c.slot_blocks(s2) == [3, 4]

    def test_fragmentation_interleaved_retires(self):
        """Interleaved long/short retires scatter the free list; the
        fully-indirect table makes any sufficient set of free blocks
        usable (no contiguity requirement)."""
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model()
        c = PagedKVCache(model, slots=3, num_blocks=8, block_size=4)
        a, b, d = c.allocate(), c.allocate(), c.allocate()
        assert c.ensure_blocks(a, 11)  # blocks 0,1,2
        assert c.ensure_blocks(b, 3)  # block 3
        assert c.ensure_blocks(d, 15)  # blocks 4,5,6,7 — pool exhausted
        assert c.free_blocks == 0
        c.free(b)  # punch a hole mid-pool
        c.free(a)
        # free list is now [3, 0, 1, 2] — non-contiguous ids
        s = c.allocate()
        assert c.ensure_blocks(s, 13)  # needs 4: takes the scattered set
        assert c.slot_blocks(s) == [3, 0, 1, 2]
        assert c.block_tables[s, :4].tolist() == [3, 0, 1, 2]
        # logical order is the TABLE's order, independent of physical ids
        assert c.free_blocks == 0

    def test_out_of_blocks_is_all_or_nothing(self):
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model()
        c = PagedKVCache(model, slots=2, num_blocks=8, block_size=4)
        a, b = c.allocate(), c.allocate()
        assert c.ensure_blocks(a, 27)  # 7 blocks
        assert c.free_blocks == 1
        # b needs 3 blocks but only 1 is free: refuse and allocate NOTHING
        assert not c.ensure_blocks(b, 11)
        assert c.free_blocks == 1 and c.slot_blocks(b) == []
        assert c.ensure_blocks(b, 3)  # what fits still lands
        assert c.free_blocks == 0

    def test_validation(self):
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model()
        with pytest.raises(ValueError, match="block_size"):
            PagedKVCache(model, slots=1, block_size=0)
        with pytest.raises(ValueError, match="cannot hold"):
            PagedKVCache(model, slots=1, num_blocks=2, block_size=4)
        c = PagedKVCache(model, slots=2, num_blocks=8, block_size=4)
        with pytest.raises(ValueError, match="not allocated"):
            c.ensure_blocks(0, 0)
        with pytest.raises(ValueError, match="not allocated"):
            c.free(0)
        s = c.allocate()
        with pytest.raises(ValueError, match="outside"):
            c.ensure_blocks(s, 32)  # table covers 8 blocks x 4 = 0..31

    def test_bytes_accounting(self):
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model()
        cfg = model.cfg
        c = PagedKVCache(model, slots=2, num_blocks=8, block_size=4)
        per_block = 2 * cfg.n_layers * 4 * cfg.kv_heads * cfg.head_dim * 4
        assert c.bytes_per_block == per_block
        dense = (
            2 * cfg.n_layers * cfg.max_seq_len * cfg.kv_heads
            * cfg.head_dim * 4
        )
        assert c.dense_bytes_per_request == dense
        s = c.allocate()
        c.ensure_blocks(s, 5)  # 2 blocks
        assert c.bytes_live == 2 * per_block
        assert c.pool_utilization == pytest.approx(2 / 8)


class TestPagedParity:
    @pytest.mark.parametrize("chunk", [2, 4, 7])
    def test_greedy_token_exact_chunked(self, no_fault_plan, chunk):
        """ACCEPTANCE: chunked-prefill outputs are token-exact vs the
        non-batched generate() path — chunk sizes that divide, straddle,
        and exceed prompt lengths all land identically."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(5, 7, 3, 6, 4)
        budgets = [6, 4, 9, 5, 7]
        eng = ServeEngine(
            model, params, slots=2, min_bucket=4,
            prefill_chunk_tokens=chunk,
        )
        rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
        out = eng.run(max_steps=500)
        assert eng.metrics.completed == len(prompts)
        for p, m, r in zip(prompts, budgets, rids):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], m)
            )[0]
            np.testing.assert_array_equal(np.asarray(out[r].tokens), ref)

    def test_greedy_token_exact_under_preemption(self, no_fault_plan):
        """A pool too small for every slot's worst case forces
        youngest-first preemption mid-stream; every request still
        completes token-exact (requeued work replays from its seed)."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(8, 9, 7, 10)
        budgets = [12, 11, 13, 10]  # worst cases ~5-6 blocks each
        # 8 blocks x 4 = 32 positions: one worst-case request fits (the
        # submit() guarantee) but two concurrent ones contend
        eng = ServeEngine(
            model, params, slots=2, min_bucket=4,
            block_size=4, pool_blocks=8,
        )
        rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
        out = eng.run(max_steps=1000)
        assert eng.metrics.completed == len(prompts)
        assert eng.metrics.preempted > 0  # pressure actually happened
        for p, m, r in zip(prompts, budgets, rids):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], m)
            )[0]
            np.testing.assert_array_equal(np.asarray(out[r].tokens), ref)
        # retirement returned every block
        assert eng.cache.live_blocks == 0

    def test_sampling_reproducible_chunked(self, no_fault_plan):
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(5, 6)

        def run_once():
            eng = ServeEngine(
                model, params, slots=2, temperature=0.8, top_k=8,
                min_bucket=4, prefill_chunk_tokens=3,
            )
            rids = [
                eng.submit(p, 5, seed=7 + i)
                for i, p in enumerate(prompts)
            ]
            out = eng.run(max_steps=200)
            return [out[r].tokens for r in rids]

        assert run_once() == run_once()

    def test_prefill_chunk_fault_replays_exactly(self, no_fault_plan):
        """CHAOS: a transient fault at serve.prefill_chunk requeues the
        half-prefilled request (blocks freed); the replay is
        token-identical to the fault-free run."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(9, 7, 5)
        budgets = [5, 6, 4]

        clean = ServeEngine(
            model, params, slots=2, min_bucket=4, prefill_chunk_tokens=3
        )
        crids = [clean.submit(p, m) for p, m in zip(prompts, budgets)]
        want = clean.run(max_steps=400)

        faults.install_plan(
            [{"point": "serve.prefill_chunk", "action": "reset",
              "after": 2}],
            export_env=False,
        )
        eng = ServeEngine(
            model, params, slots=2, min_bucket=4, prefill_chunk_tokens=3
        )
        rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
        out = eng.run(max_steps=600)
        assert eng.metrics.requeued >= 1
        assert eng.metrics.completed == len(prompts)
        for cr, r in zip(crids, rids):
            assert want[cr].tokens == out[r].tokens
        assert eng.cache.live_blocks == 0


class TestChunkedTTFT:
    def _replay(self, chunk):
        """Drive a long-prompt burst + trickling shorts under a
        deterministic token-cost clock (prefill costs its chunk length,
        a decode step costs 1): the wall-clock mechanism serve_bench
        measures, with the noise removed. Returns the short requests'
        TTFT list."""
        from pytorch_distributed_example_tpu.serve import (
            ServeEngine,
            ServeMetrics,
        )

        model, params = _model()
        fc = _FakeClock()
        # slots cover the whole trace so the comparison isolates
        # PREFILL scheduling (not slot contention, which hits both
        # modes identically)
        eng = ServeEngine(
            model, params, slots=10, min_bucket=4, clock=fc,
            metrics=ServeMetrics(clock=fc, slots=10),
            prefill_chunk_tokens=chunk,
        )
        orig_pc, orig_step = eng._prefill_chunk, eng._step

        def pc(params_, tree, chunk_, bt, start):
            fc.t += chunk_.shape[1]
            return orig_pc(params_, tree, chunk_, bt, start)

        def st(*a):
            fc.t += 1.0
            return orig_step(*a)

        eng._prefill_chunk, eng._step = pc, st

        longs = _prompts(24, 24, 24, 24, seed=1)
        shorts = _prompts(4, 5, 6, 4, 5, 6, seed=2)
        traffic = [(0.0, p, 3) for p in longs] + [
            (2.0 + 3.0 * i, p, 3) for i, p in enumerate(shorts)
        ]
        short_rids = []
        i = 0
        while i < len(traffic) or eng.pending:
            while i < len(traffic) and traffic[i][0] <= fc.t:
                # a request that hit the front door mid-step can only
                # be submitted between steps — pass its TRUE trace
                # arrival, or the wait it already served behind the
                # burst would vanish from its TTFT
                arrival, p, m = traffic[i]
                rid = eng.submit(p, m, arrival_time=arrival)
                if i >= len(longs):
                    short_rids.append(rid)
                i += 1
            if not eng.step() and i < len(traffic):
                fc.t = max(fc.t, traffic[i][0])
        assert eng.metrics.completed == len(traffic)
        return [eng.completions[r].ttft_s for r in short_rids]

    def test_long_burst_bounded_short_ttft(self, no_fault_plan):
        """ACCEPTANCE: with a burst of long prompts in flight, chunked
        prefill gives strictly better worst-case short-request TTFT
        than unchunked on the same trace — a short arrival never waits
        behind a whole long prefill, only behind one chunk."""
        unchunked = self._replay(None)
        chunked = self._replay(4)
        assert max(chunked) < max(unchunked)
        # and the bound is structural, not luck: every chunked short
        # TTFT beats the unchunked WORST case
        assert max(chunked) < max(unchunked) / 2


class TestBackpressureAndShed:
    def test_admission_waits_for_pool(self, no_fault_plan):
        """Admission stalls while the pool cannot hold a first chunk and
        resumes after retires free blocks — nothing is lost or shed."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        prompts = _prompts(12, 12, 12, 12)
        eng = ServeEngine(
            model, params, slots=4, min_bucket=4,
            block_size=4, pool_blocks=8,
        )
        # A and B fill the pool: 3 blocks of prefill each, growing to 4
        # each (16 tokens worst case) on the first decode step
        rids = [eng.submit(p, 4) for p in prompts[:2]]
        eng.step()
        assert eng.cache.free_blocks == 0
        # C and D arrive into a dry pool: slots are free but their first
        # chunk (3 blocks) cannot land — the gate holds them QUEUED
        rids += [eng.submit(p, 4) for p in prompts[2:]]
        eng.step()
        assert eng.num_active == 2 and eng.queue.depth == 2
        assert eng.metrics.preempted == 0  # the gate, not eviction
        out = eng.run(max_steps=600)
        assert eng.metrics.completed == 4
        assert all(r in out for r in rids)
        assert eng.metrics.shed == 0 and eng.metrics.preempted == 0

    def test_bounded_queue_sheds_with_metrics(self, no_fault_plan):
        from pytorch_distributed_example_tpu.serve import (
            QueueFullError,
            ServeEngine,
        )

        model, params = _model()
        prompts = _prompts(4, 4, 4, 4)
        eng = ServeEngine(
            model, params, slots=1, min_bucket=4, max_queue_depth=2
        )
        eng.submit(prompts[0], 2)
        eng.submit(prompts[1], 2)
        with pytest.raises(QueueFullError):
            eng.submit(prompts[2], 2)
        assert eng.metrics.shed == 1
        assert eng.metrics.snapshot()["shed"] == 1
        eng.run(max_steps=200)
        assert eng.metrics.completed == 2  # shed request never enqueued

    def test_requeue_exempt_from_depth_bound(self, no_fault_plan):
        """Fault-recovery requeues of already-accepted work must never
        be shed by the engine's own retry path."""
        from pytorch_distributed_example_tpu.serve import (
            Request,
            RequestQueue,
        )

        q = RequestQueue(max_depth=1)
        q.put(Request(prompt=np.ones(3, np.int32), max_new_tokens=2))
        inflight = Request(prompt=np.ones(3, np.int32), max_new_tokens=2)
        q.requeue_front(inflight)  # over depth, still accepted
        assert q.depth == 2
        assert q.pop().rid == inflight.rid  # and at the HEAD


class TestPoolMetrics:
    def test_dense_reduction_at_least_4x(self, no_fault_plan):
        """ACCEPTANCE (runtime-observable form): on a bimodal short/long
        mix, mean live cache bytes per request is >= 4x below the dense
        per-slot constant."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model(max_seq_len=64)
        prompts = _prompts(6, 10, 8, 7, 9, 6)
        budgets = [4, 12, 5, 4, 10, 5]  # live <= 22 tokens vs dense 64
        eng = ServeEngine(
            model, params, slots=3, min_bucket=4, block_size=4
        )
        for p, m in zip(prompts, budgets):
            eng.submit(p, m)
        eng.run(max_steps=600)
        snap = eng.metrics.snapshot()
        pool = snap["cache_pool"]
        assert pool["dense_reduction_x"] >= 4.0
        assert pool["bytes_per_live_request_mean"] > 0
        assert (
            pool["dense_bytes_per_request"]
            == eng.cache.dense_bytes_per_request
        )
        # drained engine: gauges read an empty pool
        assert pool["blocks_total"] == eng.cache.num_blocks
        assert eng.cache.live_blocks == 0

    def test_serve_route_reports_cache_pool(self, no_fault_plan):
        from pytorch_distributed_example_tpu.serve import ServeEngine
        from pytorch_distributed_example_tpu.utils.debug_http import (
            DebugServer,
        )

        model, params = _model()
        (prompt,) = _prompts(4)
        eng = ServeEngine(model, params, slots=1, min_bucket=4)
        eng.submit(prompt, 3)
        eng.run(max_steps=100)
        srv = DebugServer()
        try:
            srv.register_serve_metrics("engine", eng.metrics)
            with urllib.request.urlopen(srv.url + "/serve") as r:
                doc = json.loads(r.read())
            pool = doc["engine"]["cache_pool"]
            assert pool["blocks_total"] > 0
            assert "utilization" in pool and "bytes_live" in pool
            assert "dense_reduction_x" in pool
        finally:
            srv.shutdown()


class TestTensorParallelDecode:
    def test_tp2_token_exact_vs_generate(self, no_fault_plan):
        """ACCEPTANCE (tier-1, 2 virtual CPU devices): TP decode over a
        ("tp", 2) mesh — params Megatron-sharded, block pool KV-head-
        sharded, slot lanes replicated — produces token-exact greedy
        outputs vs single-device generate(), chunked prefill on."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        mesh = _tp_mesh(2)
        prompts = _prompts(5, 7, 3, 6)
        budgets = [6, 4, 9, 5]
        eng = ServeEngine(
            model, params, slots=2, min_bucket=4, mesh=mesh,
            prefill_chunk_tokens=4,
        )
        rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
        out = eng.run(max_steps=500)
        assert eng.metrics.completed == len(prompts)
        for p, m, r in zip(prompts, budgets, rids):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], m)
            )[0]
            np.testing.assert_array_equal(np.asarray(out[r].tokens), ref)

    def test_tp2_pool_sharded_on_kv_heads(self, no_fault_plan):
        """The block pool actually lands KV-head-sharded (not silently
        replicated) and the slot lanes replicated."""
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        mesh = _tp_mesh(2)
        eng = ServeEngine(model, params, slots=2, min_bucket=4, mesh=mesh)
        k = eng.cache.tree["layers_0"]["attn"]["k"]
        assert k.sharding.spec == P(None, None, "tp", None)
        assert eng._dev_lengths.sharding.spec == P()
        # param sharding followed the Megatron rules (spot check)
        q = eng.params["layers_0"]["attn"]["q_proj"]["kernel"]
        assert "tp" in (q.sharding.spec[-1] or ())

class TestDecodeKernel:
    """The decode step at a head size `ops.paged_kernel` accepts
    (Dh = 128): the step program runs `ops.paged_decode_attention`
    (interpreted here), and prefill chunks that fill a sublane tile
    `ops.paged_chunk_attention`."""

    @staticmethod
    def _wide_model():
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import (
            TransformerConfig,
            TransformerLM,
        )

        cfg = TransformerConfig(
            vocab_size=64, d_model=512, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=64, max_seq_len=64, use_flash=False,
        )
        model = TransformerLM(cfg)
        return model, model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )

    @pytest.mark.parametrize("tp", [1, 2])
    def test_greedy_token_exact_and_every_step_counted(
        self, no_fault_plan, tp
    ):
        """Greedy tokens equal `generate()`'s, exactly: in float32 the
        kernel differs from the dense path by reassociation (~1e-6 of a
        logit) and no argmax of these prompts sits that close to a tie,
        so no match-rate bound is needed. Slots retire and park while
        others decode, so parked lanes ride through the kernel too.
        `tp=2` runs it per device on its KV-head shard."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = self._wide_model()
        prompts = _prompts(5, 19, 3, 11, 26)
        budgets = [9, 4, 30, 12, 7]
        eng = ServeEngine(
            model, params, slots=3, min_bucket=4, block_size=8,
            prefill_chunk_tokens=8, mesh=_tp_mesh(2) if tp == 2 else None,
        )
        rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
        out = eng.run(max_steps=500)
        assert eng.metrics.completed == len(prompts)
        for p, m, r in zip(prompts, budgets, rids):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], m)
            )[0]
            np.testing.assert_array_equal(np.asarray(out[r].tokens), ref)
        snap = eng.metrics.snapshot()
        decode, prefill = snap["decode"], snap["prefill"]
        assert decode["steps"] > 0
        assert decode["kernel_steps"] == decode["steps"]
        assert decode["kernel_share"] == 1.0
        # the 8-token chunks fill a float32 sublane tile and take the
        # chunk kernel; a prompt's 4-token tail bucket fills none, and
        # `ops.paged_kernel` leaves it to the gather + einsum
        assert prefill["chunks"] > len(prompts)
        assert 0.0 < prefill["kernel_share"] < 1.0

    @pytest.mark.parametrize("engine", ["tp1", "tp2", "prefix_cache"])
    def test_every_chunk_takes_the_chunk_kernel(self, no_fault_plan, engine):
        """Buckets from 8 tokens up: every prefill chunk fills a sublane
        tile, so every (chunk, layer) attention call is
        `ops.paged_chunk_attention` — later chunks at a nonzero `start`,
        under tp per device on its KV-head shard, and with the prefix
        cache a FIRST chunk that starts behind another request's blocks.
        Tokens stay `generate()`'s, exactly."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = self._wide_model()
        prompts = _prompts(21, 8, 30, 13)
        if engine == "prefix_cache":
            # the same 16 leading tokens: two whole blocks to share
            prompts = [np.concatenate([prompts[2][:16], p]) for p in prompts]
        eng = ServeEngine(
            model, params, slots=2, min_bucket=8, block_size=8,
            prefill_chunk_tokens=8, mesh=_tp_mesh(2) if engine == "tp2" else None,
            prefix_cache=engine == "prefix_cache",
        )
        rids = [eng.submit(p, 6) for p in prompts]
        out = eng.run(max_steps=500)
        assert eng.metrics.completed == len(prompts)
        for p, r in zip(prompts, rids):
            ref = np.asarray(generate(model, params, jnp.asarray(p)[None], 6))
            np.testing.assert_array_equal(np.asarray(out[r].tokens), ref[0])
        snap = eng.metrics.snapshot()
        prefill = snap["prefill"]
        assert prefill["kernel_share"] == 1.0
        assert prefill["kernel_calls"] == prefill["chunks"] * model.cfg.n_layers
        whole = sum(-(-len(p) // 8) for p in prompts)
        if engine == "prefix_cache":
            assert snap["prefix_cache"]["hits"] > 0
            assert prefill["chunks"] < whole  # shared blocks were not prefilled
        else:
            assert prefill["chunks"] == whole

    def test_tp2_one_token_chunks_run_the_kernel_per_device(
        self, no_fault_plan
    ):
        """A prefill chunk of ONE token is a decode call to the model
        (L == 1), so it takes the kernel too — under a tp mesh inside
        the same `partitioned_over` context as the step, or GSPMD would
        be handed a Mosaic call it cannot partition on the chip. Here:
        the chunk program lowers with the kernel inside a `shard_map`,
        and the engine's tokens stay `generate()`'s."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = self._wide_model()
        eng = ServeEngine(
            model, params, slots=2, min_bucket=4, block_size=8,
            prefill_chunk_tokens=1, mesh=_tp_mesh(2),
        )
        text = eng._prefill_chunk.lower(
            eng.params, eng.cache.tree, np.zeros((1, 1), np.int32),
            eng.cache.block_tables[:1], 0,
        ).as_text(debug_info=True)
        assert "paged_decode_attention" in text and "shard_map" in text
        (prompt,) = _prompts(6)
        rid = eng.submit(prompt, 5)
        out = eng.run(max_steps=100)
        ref = np.asarray(generate(model, params, jnp.asarray(prompt)[None], 5))
        np.testing.assert_array_equal(np.asarray(out[rid].tokens), ref[0])

    @pytest.mark.parametrize("head", ["tiny_head", "kernel_head"])
    def test_precompiled_engine_runs_the_saved_programs(
        self, no_fault_plan, monkeypatch, head
    ):
        """The resize fast path (`serve/prewarm.py`): an engine given
        pre-warmed executables serves through them — no call reaches
        the jit quadruple behind them — and emits the tokens of an engine
        without them, kernel step or gather step. The executables are
        the ones `prewarm_engine_programs` hands to be saved, keyed as
        `load_precompiled` returns them; they skip the round trip
        through a file because under this harness (8 virtual devices,
        fusion emitters off) XLA:CPU refuses to run an executable it
        deserialized ("Function ..._fusion not found"); the chip runs
        them (PERF.md, PR 25)."""
        from pytorch_distributed_example_tpu.serve import ServeEngine, prewarm

        model, params = _model() if head == "tiny_head" else self._wide_model()
        kw = dict(slots=2, min_bucket=4, block_size=8, prefill_chunk_tokens=8)
        cold = ServeEngine(model, params, **kw)
        assert cold._decode_kernel == (head == "kernel_head")
        saved = {}
        monkeypatch.setattr(
            prewarm, "_save_precompiled",
            lambda compiled, save_dir, tp=1: saved.update(compiled),
        )
        prewarm.prewarm_engine_programs(cold, save_dir="unused")
        assert set(saved) == {
            ("prefill_chunk", 8), ("first_token", 8), ("attach", 2),
            ("step", 2),
        }
        warm = ServeEngine(model, params, precompiled=saved, **kw)

        def traced(*args):
            raise AssertionError("a call fell through to the jit program")

        for prog in (
            warm._prefill_chunk, warm._first_token, warm._attach, warm._step
        ):
            assert isinstance(prog, prewarm._ChunkDispatch)
            prog._fallback = traced
        # lengths whose every chunk is the pre-warmed width (a shorter
        # tail takes its own bucket's program, unwarmed by design)
        prompts = _prompts(5, 13)
        rids = [warm.submit(p, 6) for p in prompts]
        out = warm.run(max_steps=100)
        assert warm.metrics.completed == 2
        ref_rids = [cold.submit(p, 6) for p in prompts]
        ref = cold.run(max_steps=100)
        for r, rr in zip(rids, ref_rids):
            assert out[r].tokens == ref[rr].tokens

    @pytest.mark.parametrize("engine", ["tiny_head", "int8_pool"])
    def test_other_engines_count_no_kernel_step(self, no_fault_plan, engine):
        """The counter names the path the step program took: the tiny
        heads of the other tests and an int8 pool keep the gather."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        if engine == "tiny_head":
            model, params = _model()
            eng = ServeEngine(model, params, slots=2, min_bucket=4)
        else:
            model, params = self._wide_model()
            eng = ServeEngine(
                model, params, slots=2, min_bucket=4, block_size=8,
                kv_quant=True,
            )
        eng.submit(_prompts(5)[0], 4)
        eng.run(max_steps=100)
        snap = eng.metrics.snapshot()
        decode, prefill = snap["decode"], snap["prefill"]
        assert decode["steps"] > 0 and decode["kernel_steps"] == 0
        assert decode["kernel_share"] == 0.0
        assert prefill["chunks"] > 0 and prefill["kernel_calls"] == 0
        assert prefill["kernel_share"] == 0.0


_TRAINED_CACHE = {}


def _trained_model(max_seq_len=48, steps=150):
    """Tiny LM briefly pretrained on the deterministic bigram chain via
    the `tests._recipes.chain_pretrain` recipe (see its
    docstring: greedy decode on random-init weights argmaxes over
    near-tied logits, so a match-rate test there measures argmax noise,
    not cache fidelity — trained margins make token flips attributable
    to quantization)."""
    from tests._recipes import chain_pretrain

    if (max_seq_len, steps) in _TRAINED_CACHE:
        return _TRAINED_CACHE[(max_seq_len, steps)]
    model, params = _model(max_seq_len=max_seq_len)
    params, chain, _ = chain_pretrain(
        model, params, train_len=max_seq_len, steps=steps, seed=7
    )
    _TRAINED_CACHE[(max_seq_len, steps)] = (model, params, chain)
    return model, params, chain


class TestQuantizedKV:
    def test_quantized_pool_layout_and_capacity(self):
        """int8 pool: K/V int8 + per-(token, kv-head) f32 scale planes;
        bytes accounting includes the scale overhead; at FIXED pool
        bytes the int8 pool holds >= 1.8x the worst-case requests."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model()
        cfg = model.cfg
        f = PagedKVCache(model, slots=2, num_blocks=8, block_size=4)
        q = PagedKVCache(
            model, slots=2, num_blocks=8, block_size=4, quantized=True
        )
        layer = q.tree["layers_0"]["attn"]
        assert layer["k"].dtype == jnp.int8 and layer["v"].dtype == jnp.int8
        assert layer["k_scale"].dtype == jnp.float32
        assert layer["k_scale"].shape == (8, 4, cfg.kv_heads)
        scale_b = 2 * cfg.n_layers * 4 * cfg.kv_heads * 4
        payload_b = 2 * cfg.n_layers * 4 * cfg.kv_heads * cfg.head_dim
        assert q.scale_bytes_per_block == scale_b
        assert q.bytes_per_block == payload_b + scale_b
        assert f.scale_bytes_per_block == 0
        assert q.wire_dtype == "int8" and f.wire_dtype == "float32"
        # fixed-byte capacity: same pool bytes -> >= 1.8x the blocks,
        # and effective (worst-case-request) slots scale with them
        blocks_q = (f.num_blocks * f.bytes_per_block) // q.bytes_per_block
        assert blocks_q / f.num_blocks >= 1.8
        big = PagedKVCache(
            model, slots=2, num_blocks=int(blocks_q), block_size=4,
            quantized=True,
        )
        assert big.effective_slots >= int(1.8 * f.effective_slots)

    def test_quantized_greedy_match_rate_vs_f32(self, no_fault_plan):
        """ACCEPTANCE: on a trained model, int8-KV greedy decode matches
        the f32 cache's token stream at >= 0.99 per-token rate."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params, chain = _trained_model()
        gen = np.random.default_rng(3)
        prompts = [
            chain(int(gen.integers(0, 64)), int(n))
            for n in gen.integers(6, 16, 8)
        ]
        budgets = [int(b) for b in gen.integers(8, 24, 8)]

        def run(kv_quant):
            eng = ServeEngine(
                model, params, slots=4, min_bucket=4,
                prefill_chunk_tokens=4, kv_quant=kv_quant,
            )
            rids = [
                eng.submit(p, m) for p, m in zip(prompts, budgets)
            ]
            out = eng.run(max_steps=2000)
            assert eng.metrics.completed == len(prompts)
            return [out[r].tokens for r in rids]

        ref, got = run(False), run(True)
        matched = sum(
            int(a == b) for ra, rb in zip(ref, got) for a, b in zip(ra, rb)
        )
        total = sum(len(r) for r in ref)
        assert matched / total >= 0.99, f"match rate {matched / total:.4f}"

    def test_quantized_preemption_replays_identically(self, no_fault_plan):
        """Preempted int8-KV requests replay token-identically: the
        per-token scales make quantize-on-scatter deterministic and
        independent of write batching, so a from-seed replay (and a run
        with no pool pressure at all) lands the same stream."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params, chain = _trained_model()
        prompts = [chain(s, n) for s, n in [(3, 8), (11, 9), (23, 7), (41, 10)]]
        budgets = [12, 11, 13, 10]

        def run(pool_blocks, slots=3):
            eng = ServeEngine(
                model, params, slots=slots, min_bucket=4, block_size=4,
                pool_blocks=pool_blocks, prefill_chunk_tokens=3,
                kv_quant=True,
            )
            rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
            out = eng.run(max_steps=2000)
            assert eng.metrics.completed == len(prompts)
            assert eng.cache.live_blocks == 0
            return eng, [out[r].tokens for r in rids]

        # 12 blocks x 4 = one max-seq worst case (the submit() floor);
        # three ~5-block requests contend -> youngest-first preemption
        tight_eng, tight = run(12)
        assert tight_eng.metrics.preempted > 0
        _, tight2 = run(12)
        ample_eng, ample = run(64)  # no pressure at all
        assert ample_eng.metrics.preempted == 0
        assert tight == tight2  # deterministic under preemption
        assert tight == ample  # and identical to the pressure-free run

    def test_quantized_chaos_prefill_fault_replay(self, no_fault_plan):
        """The serve.prefill_chunk chaos contract holds quantized: a
        transient fault requeues and the replay is token-identical."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params, chain = _trained_model()
        prompts = [chain(s, n) for s, n in [(5, 9), (17, 7), (29, 5)]]
        budgets = [5, 6, 4]

        def run(plan):
            if plan:
                faults.install_plan(plan, export_env=False)
            eng = ServeEngine(
                model, params, slots=2, min_bucket=4,
                prefill_chunk_tokens=3, kv_quant=True,
            )
            rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
            out = eng.run(max_steps=800)
            faults.clear_plan()
            assert eng.metrics.completed == len(prompts)
            return eng, [out[r].tokens for r in rids]

        _, want = run(None)
        eng, got = run(
            [{"point": "serve.prefill_chunk", "action": "reset", "after": 2}]
        )
        assert eng.metrics.requeued >= 1
        assert got == want

    def test_quantized_tp2_matches_single_device(self, no_fault_plan):
        """TP2 decode over the KV-head-sharded int8 pool (scale planes
        sharded alongside) produces the same tokens as the single-device
        quantized engine, chunked prefill on."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params, chain = _trained_model()
        mesh = _tp_mesh(2)
        prompts = [chain(s, n) for s, n in [(2, 6), (9, 8), (31, 5)]]
        budgets = [6, 5, 7]

        def run(mesh_):
            eng = ServeEngine(
                model, params, slots=2, min_bucket=4, mesh=mesh_,
                prefill_chunk_tokens=4, kv_quant=True,
            )
            rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
            out = eng.run(max_steps=800)
            assert eng.metrics.completed == len(prompts)
            return eng, [out[r].tokens for r in rids]

        _, single = run(None)
        eng, tp = run(mesh)
        assert tp == single
        # after a run the cache leaves are jit outputs, whose inferred
        # specs may drop trailing Nones — pin the KV-head axis entry
        layer = eng.cache.tree["layers_0"]["attn"]
        assert tuple(layer["k"].sharding.spec)[:3] == (None, None, "tp")
        assert tuple(layer["k_scale"].sharding.spec)[:3] == (
            None, None, "tp",
        )

    def test_serve_route_reports_wire_format(self, no_fault_plan):
        """SATELLITE: /serve exposes the cache wire dtype, the scale
        overhead bytes, and effective slots-per-chip."""
        import json
        import urllib.request

        from pytorch_distributed_example_tpu.serve import ServeEngine
        from pytorch_distributed_example_tpu.utils.debug_http import (
            DebugServer,
        )

        model, params = _model()
        (prompt,) = _prompts(4)
        eng = ServeEngine(
            model, params, slots=1, min_bucket=4, kv_quant=True
        )
        eng.submit(prompt, 3)
        eng.run(max_steps=100)
        srv = DebugServer()
        try:
            srv.register_serve_metrics("engine", eng.metrics)
            with urllib.request.urlopen(srv.url + "/serve") as r:
                doc = json.loads(r.read())
            pool = doc["engine"]["cache_pool"]
            assert pool["wire_dtype"] == "int8"
            assert pool["scale_overhead_bytes"] > 0
            assert pool["effective_slots"] == eng.cache.effective_slots
        finally:
            srv.shutdown()


class TestTensorParallelDecodeWide:
    @pytest.mark.slow
    def test_tp4_multichip_trace(self, no_fault_plan):
        """Wider-mesh serving smoke (slow tier): a mixed trace with
        chunked prefill + preemption pressure on a ("tp", 4) mesh stays
        token-exact and drains the pool."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        mesh = _tp_mesh(4)
        prompts = _prompts(5, 9, 3, 7, 12, 4, 8, 6)
        budgets = [6, 4, 9, 5, 7, 3, 8, 4]
        eng = ServeEngine(
            model, params, slots=4, min_bucket=4, mesh=mesh,
            prefill_chunk_tokens=4, block_size=4, pool_blocks=16,
        )
        rids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
        out = eng.run(max_steps=2000)
        assert eng.metrics.completed == len(prompts)
        for p, m, r in zip(prompts, budgets, rids):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], m)
            )[0]
            np.testing.assert_array_equal(np.asarray(out[r].tokens), ref)
        assert eng.cache.live_blocks == 0
