"""Prefix-sharing paged KV tests (ISSUE 12): refcounted blocks +
copy-on-write in `serve/cache.py`, the radix prefix index
(`serve/prefix.py`), and the engine attach path.

Coverage map:
* `TestRefcountCoW` — block refcount lifecycle (attach/free/decrement,
  shared-counted-ONCE pool introspection: `bytes_live`,
  `pool_utilization`, `effective_slots`, `dense_bytes_per_request`),
  copy-on-write semantics (quantized scale planes included), and the
  cached-free reclaim path that invalidates index entries LRU.
* `TestRadixIndex` — pure index behavior: full-block walks, partial
  tails, longest-common-prefix divergence, the L-1 cap, scope
  isolation, duplicate-insert descend, subtree eviction.
* `TestPrefixParity` — ACCEPTANCE: token-exact outputs with sharing on
  vs off across greedy and seeded-sampling runs, including under
  preemption + replay and with `kv_quant=True`.
* `TestPrefixChaos` — the `serve.prefix_attach` fault point: a
  transient fault at attach requeues and the replay re-attaches the
  shared blocks, token-exact.
* `TestTenantIsolation` — two tenants with identical preambles share
  NOTHING unless both `ClassSpec`s opt in; opted-in sharing never
  changes served tokens (no decoded-token leakage).
* `TestPrefixMetrics` — `/serve` exposes the prefix_cache block.
"""

import json
import urllib.request

import numpy as np
import pytest

from pytorch_distributed_example_tpu import faults


def _model(max_seq_len=48):
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


def _preamble_prompts(pre_len, suffix_lens, seed=0, vocab=64):
    """One shared preamble + unique suffixes — the sharing trace."""
    gen = np.random.default_rng(seed)
    pre = gen.integers(0, vocab, (pre_len,)).astype(np.int32)
    return pre, [
        np.concatenate([pre, gen.integers(0, vocab, (n,)).astype(np.int32)])
        for n in suffix_lens
    ]


@pytest.fixture()
def no_fault_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


class TestRefcountCoW:
    def test_attach_refcount_lifecycle(self):
        """attach_prefix increments refcounts; free() decrements and a
        shared block survives its first holder; the pool counts every
        shared block ONCE."""
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model(max_seq_len=32)
        c = PagedKVCache(model, slots=3, num_blocks=8, block_size=4)
        a = c.allocate()
        assert c.ensure_blocks(a, 11)  # blocks 0,1,2
        blocks = c.slot_blocks(a)
        b = c.allocate()
        c.attach_prefix(b, blocks[:2])
        assert [c.refcount(x) for x in blocks] == [2, 2, 1]
        # shared counted once: 3 physical blocks live, not 5 references
        assert c.live_blocks == 3
        assert c.total_block_refs == 5
        assert c.shared_blocks == 2
        assert c.bytes_live == 3 * c.bytes_per_block
        assert c.bytes_deduplicated == 2 * c.bytes_per_block
        assert c.pool_utilization == pytest.approx(3 / 8)
        # layout-derived capacity figures are sharing-independent
        assert c.effective_slots == 8 // c.blocks_per_seq
        assert c.dense_bytes_per_request == (
            2 * model.cfg.n_layers * model.cfg.max_seq_len
            * model.cfg.kv_heads * model.cfg.head_dim * 4
        )
        assert c.exclusive_blocks(a) == 1 and c.exclusive_blocks(b) == 0
        # freeing the ORIGINAL holder reclaims only its exclusive block
        assert c.free(a) == 1
        assert [c.refcount(x) for x in blocks] == [1, 1, 0]
        assert c.live_blocks == 2
        assert c.free(b) == 2
        assert c.live_blocks == 0 and c.free_blocks == 8

    def test_cow_copies_shared_block_and_scales(self):
        """Writing into a shared block first copies it — pool K/V AND
        the int8 scale planes — leaving the original untouched for the
        other holder."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model(max_seq_len=32)
        c = PagedKVCache(
            model, slots=2, num_blocks=8, block_size=4, quantized=True
        )
        a = c.allocate()
        assert c.ensure_blocks(a, 7)  # blocks 0,1
        # stamp recognizable content into block 1 across every leaf
        layer = c.tree["layers_0"]["attn"]
        c.tree["layers_0"]["attn"] = {
            "k": layer["k"].at[1].set(7),
            "v": layer["v"].at[1].set(9),
            "k_scale": layer["k_scale"].at[1].set(0.5),
            "v_scale": layer["v_scale"].at[1].set(0.25),
        }
        b = c.allocate()
        c.attach_prefix(b, c.slot_blocks(a))
        assert c.needs_cow(b, 5) and c.needs_cow(a, 5)
        assert c.cow_block(b, 5)  # b diverges inside logical block 1
        nb = c.slot_blocks(b)[1]
        assert nb != 1 and c.refcount(1) == 1 and c.refcount(nb) == 1
        assert c.block_tables[b, 1] == nb
        assert c.cow_copies == 1
        layer = c.tree["layers_0"]["attn"]
        # copy carries payload AND scales; original intact
        assert (np.asarray(layer["k"][nb]) == 7).all()
        assert (np.asarray(layer["v"][nb]) == 9).all()
        assert np.asarray(layer["k_scale"][nb]) == pytest.approx(0.5)
        assert np.asarray(layer["v_scale"][nb]) == pytest.approx(0.25)
        assert (np.asarray(layer["k"][1]) == 7).all()
        # a now needs no CoW only after b detached... a still shares
        # block 0 with b but block 1 is private again
        assert not c.needs_cow(a, 5)
        assert c.needs_cow(a, 2)  # block 0 still shared
        assert layer["k"].dtype == jnp.int8

    def test_exclusive_unindexed_block_writes_in_place(self):
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model(max_seq_len=16)
        c = PagedKVCache(model, slots=1, num_blocks=4, block_size=4)
        s = c.allocate()
        c.ensure_blocks(s, 3)
        assert not c.needs_cow(s, 2)
        assert c.cow_block(s, 2)  # no-op
        assert c.cow_copies == 0 and c.slot_blocks(s) == [0]

    def test_indexed_blocks_cached_then_reclaimed_lru(self):
        """Index-pinned blocks at refcount 0 stay reclaimable (counted
        free) but preserve content until the plain free list drains;
        reclaiming one fires the evict hook with the block id."""
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model(max_seq_len=16)
        c = PagedKVCache(model, slots=2, num_blocks=4, block_size=4)
        evicted = []
        c.evict_hook = lambda b: (evicted.append(b), c._deindex(b))
        a = c.allocate()
        c.ensure_blocks(a, 7)  # blocks 0,1
        c.mark_indexed(0)
        c.mark_indexed(1)
        c.free(a)
        assert c.free_blocks == 4  # cached blocks count as reclaimable
        assert c.cached_free_blocks == 2 and c.live_blocks == 0
        b = c.allocate()
        # blocks 2,3 (plain free list) hand out FIRST — the cache stays
        # warm while uncached blocks exist
        assert c.ensure_blocks(b, 7)
        assert c.slot_blocks(b) == [2, 3]
        assert evicted == []
        # the next growth must reclaim a cached block, oldest-freed first
        assert c.ensure_blocks(b, 11)
        assert evicted == [0]
        assert c.slot_blocks(b) == [2, 3, 0]
        assert c.cached_free_blocks == 1

    def test_cow_dry_pool_sacrifices_index_entry(self):
        """refcount-1 + index-pinned + zero free blocks: CoW drops the
        index entry instead of failing — cheaper than a preemption."""
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model(max_seq_len=16)
        c = PagedKVCache(model, slots=1, num_blocks=4, block_size=4)
        dropped = []
        c.evict_hook = lambda b: (dropped.append(b), c._deindex(b))
        s = c.allocate()
        c.ensure_blocks(s, 15)  # the whole pool
        c.mark_indexed(3)
        assert c.free_blocks == 0 and c.needs_cow(s, 13)
        assert c.cow_block(s, 13)
        assert dropped == [3]
        assert c.cow_copies == 0  # no copy happened: ownership transfer
        assert not c.needs_cow(s, 13)

    def test_cow_shared_dry_pool_fails(self):
        """A genuinely shared block with a dry pool cannot CoW — the
        False return is the engine's preemption signal."""
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model(max_seq_len=16)
        c = PagedKVCache(model, slots=2, num_blocks=4, block_size=4)
        a = c.allocate()
        c.ensure_blocks(a, 15)
        b = c.allocate()
        # 'a' frees nothing; attach b to a's first block via the cache
        # API after a releases... instead share directly:
        blocks = c.slot_blocks(a)
        c.free(a)
        a2 = c.allocate()
        c.attach_prefix(a2, blocks)
        c.attach_prefix(b, blocks[:1])
        assert c.free_blocks == 0 and c.refcount(blocks[0]) == 2
        assert not c.cow_block(b, 0)


class TestRadixIndex:
    def _cache(self, num_blocks=16, block_size=4, max_seq_len=32):
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        model, _ = _model(max_seq_len=max_seq_len)
        return PagedKVCache(
            model, slots=4, num_blocks=num_blocks, block_size=block_size
        )

    def _fill(self, c, tokens):
        """Allocate a slot holding ceil(len/bs) blocks for `tokens`."""
        s = c.allocate()
        c.ensure_blocks(s, len(tokens) - 1)
        return s, c.slot_blocks(s)

    def test_insert_match_full_and_partial(self):
        from pytorch_distributed_example_tpu.serve import PrefixIndex

        c = self._cache()
        ix = PrefixIndex(c)
        toks = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]  # 2 full blocks + tail 2
        s, blocks = self._fill(c, toks)
        assert ix.insert("t", toks, blocks) == 3
        assert ix.nodes == 3
        for b in blocks:
            assert b in c._indexed
        # identical prompt: full blocks + partial tail, capped at L-1
        got, m = ix.match("t", toks)
        assert got == blocks and m == 9  # cap: len-1
        # longer prompt diverging after the tail: same 3 blocks, the
        # tail's 2 tokens shared (partial-boundary divergence)
        got, m = ix.match("t", [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12])
        assert got == blocks and m == 10
        # divergence INSIDE block 2: two full + partial of the third
        got, m = ix.match("t", [1, 2, 3, 4, 5, 6, 7, 8, 9, 99, 98, 97])
        assert got == blocks and m == 9
        # divergence inside block 1: one full block + 2 tokens of next
        got, m = ix.match("t", [1, 2, 3, 4, 5, 6, 99, 98])
        assert got == blocks[:2] and m == 6
        # first-token miss
        got, m = ix.match("t", [9, 9, 9, 9])
        assert got == [] and m == 0

    def test_scope_isolation_and_stats(self):
        from pytorch_distributed_example_tpu.serve import PrefixIndex

        c = self._cache()
        ix = PrefixIndex(c)
        toks = list(range(1, 9))
        _, blocks = self._fill(c, toks)
        ix.insert(("tenant", "a"), toks, blocks)
        got, m = ix.match(("tenant", "b"), toks)
        assert got == [] and m == 0
        got, m = ix.match(("tenant", "a"), toks)
        assert m == 7
        st = ix.stats()
        assert st["hits"] == 1 and st["misses"] == 1
        assert st["hit_rate"] == 0.5
        assert st["prefix_tokens_reused"] == 7

    def test_duplicate_insert_descends_without_reindex(self):
        from pytorch_distributed_example_tpu.serve import PrefixIndex

        c = self._cache()
        ix = PrefixIndex(c)
        toks = list(range(1, 9))
        _, b1 = self._fill(c, toks)
        _, b2 = self._fill(c, toks)
        ix.insert("t", toks, b1)
        n = ix.nodes
        ix.insert("t", toks, b2)  # same content, different blocks
        assert ix.nodes == n  # nothing re-indexed
        got, _ = ix.match("t", toks)
        assert got == b1  # the original owns the entry

    def test_eviction_removes_subtree(self):
        """Reclaiming an interior block's entry drops its descendants
        too — a child prefix is unreachable without its parent."""
        from pytorch_distributed_example_tpu.serve import PrefixIndex

        c = self._cache(num_blocks=4, max_seq_len=16)
        ix = PrefixIndex(c)
        toks = list(range(1, 13))  # 3 blocks
        s, blocks = self._fill(c, toks)
        ix.insert("t", toks, blocks)
        c.free(s)  # refcount 0: all three park on the cached list
        assert c.cached_free_blocks == 3 and ix.nodes == 3
        # one fresh block exists (num_blocks=4); a 2-block request must
        # reclaim the OLDEST cached block — the chain root — and the
        # whole chain leaves the index
        s2 = c.allocate()
        assert c.ensure_blocks(s2, 7)
        assert ix.nodes == 0
        assert c.cached_free_blocks == 0
        got, m = ix.match("t", toks)
        assert got == [] and m == 0


class TestPrefixParity:
    def _run(self, model, params, prompts, budgets, prefix, seed0=0,
             **kw):
        from pytorch_distributed_example_tpu.serve import ServeEngine

        eng = ServeEngine(
            model, params, slots=kw.pop("slots", 2), min_bucket=4,
            prefill_chunk_tokens=kw.pop("prefill_chunk_tokens", 6),
            block_size=4, prefix_cache=prefix, **kw,
        )
        rids = [
            eng.submit(p, m, seed=seed0 + i)
            for i, (p, m) in enumerate(zip(prompts, budgets))
        ]
        out = eng.run(max_steps=4000)
        assert eng.metrics.completed == len(prompts)
        assert eng.cache.live_blocks == 0  # cached blocks count free
        return eng, [out[r].tokens for r in rids]

    def test_greedy_token_exact_and_hits(self, no_fault_plan):
        """ACCEPTANCE: sharing on vs off is token-exact (greedy), vs
        generate() too, and the shared preamble actually hits."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate

        model, params = _model()
        _, prompts = _preamble_prompts(14, [4, 6, 3, 5])
        budgets = [6, 5, 7, 4]
        _, off = self._run(model, params, prompts, budgets, False)
        eng, on = self._run(model, params, prompts, budgets, True)
        assert on == off
        assert eng.metrics.prefix_hits > 0
        assert eng.metrics.prefix_tokens_reused > 0
        for p, m, toks in zip(prompts, budgets, off):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], m)
            )[0]
            np.testing.assert_array_equal(np.asarray(toks), ref)

    def test_sampling_token_exact(self, no_fault_plan):
        """ACCEPTANCE: seeded-sampling runs land the same streams with
        sharing on and off (per-request seeds pin the rng)."""
        model, params = _model()
        _, prompts = _preamble_prompts(12, [5, 4, 6], seed=3)
        budgets = [6, 7, 5]
        _, off = self._run(
            model, params, prompts, budgets, False,
            temperature=0.8, top_k=8, seed0=11,
        )
        eng, on = self._run(
            model, params, prompts, budgets, True,
            temperature=0.8, top_k=8, seed0=11,
        )
        assert on == off and eng.metrics.prefix_hits > 0

    @pytest.mark.parametrize("kv_quant", [False, True])
    def test_token_exact_under_preemption(self, no_fault_plan, kv_quant):
        """ACCEPTANCE: a pool sized to one worst-case request forces
        preemption; replayed requests re-attach their cached prefix and
        land token-identically — f32 and int8 pools."""
        model, params = _model()
        _, prompts = _preamble_prompts(14, [4, 6, 3, 5], seed=1)
        budgets = [10, 9, 11, 8]
        _, off = self._run(
            model, params, prompts, budgets, False,
            slots=3, pool_blocks=12, kv_quant=kv_quant,
        )
        eng, on = self._run(
            model, params, prompts, budgets, True,
            slots=3, pool_blocks=12, kv_quant=kv_quant,
        )
        assert eng.metrics.preempted > 0  # pressure actually happened
        assert on == off
        # ample-pool run agrees too (preemption changed nothing)
        _, ample = self._run(
            model, params, prompts, budgets, True,
            slots=3, pool_blocks=64, kv_quant=kv_quant,
        )
        assert ample == off

    def test_pool_writes_actually_skipped(self, no_fault_plan):
        """The hit skips POOL WRITES too: a warm request leaves the
        preamble resident, then a concurrent burst SHARES those blocks
        — while every burst request decodes, the pool holds the
        preamble once (live blocks strictly below the no-sharing
        replay) and reports the dedup bytes."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        pre, prompts = _preamble_prompts(16, [4, 5, 6], seed=2)
        warm = np.concatenate([pre, np.asarray([1, 2], np.int32)])
        # budgets long enough that all three decode CONCURRENTLY even
        # in the slow (no-sharing) replay's staggered prefill schedule
        budgets = [16, 16, 16]

        def run(prefix):
            eng = ServeEngine(
                model, params, slots=3, min_bucket=4,
                prefill_chunk_tokens=6, block_size=4,
                prefix_cache=prefix,
            )
            eng.submit(warm, 2)
            eng.run(max_steps=400)
            for p, m in zip(prompts, budgets):
                eng.submit(p, m)
            # step until every burst request is decoding, then read the
            # pool at a comparable instant in both modes
            for _ in range(200):
                eng.step()
                if len(eng._decoding) == len(prompts):
                    break
            assert len(eng._decoding) == len(prompts)
            live_all_decoding = eng.cache.live_blocks
            refs_all_decoding = eng.cache.total_block_refs
            eng.run(max_steps=1500)
            assert eng.metrics.completed == len(prompts) + 1
            return eng, live_all_decoding, refs_all_decoding

        eng_off, live_off, refs_off = run(False)
        eng_on, live_on, refs_on = run(True)
        # sharing stores the preamble once: strictly fewer live blocks
        # for the same logical footprint
        assert live_on < live_off
        assert refs_on >= live_on  # references exceed physical blocks
        snap = eng_on.metrics.snapshot()["prefix_cache"]
        assert snap["peak_bytes_deduplicated"] > 0
        assert snap["hits"] == len(prompts)


class TestPrefixChaos:
    def test_prefix_attach_fault_requeues_and_replays_exact(
        self, no_fault_plan
    ):
        """CHAOS (satellite): a transient fault at serve.prefix_attach
        requeues the request before anything was attached; the replay
        re-attaches the SAME shared blocks and the stream is
        token-identical to the fault-free run."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        _, prompts = _preamble_prompts(14, [5, 4, 6], seed=4)
        budgets = [5, 6, 4]

        def run(plan):
            faults.clear_plan()
            if plan:
                faults.install_plan(plan, export_env=False)
            eng = ServeEngine(
                model, params, slots=2, min_bucket=4,
                prefill_chunk_tokens=6, block_size=4, prefix_cache=True,
            )
            rids = [
                eng.submit(p, m) for p, m in zip(prompts, budgets)
            ]
            out = eng.run(max_steps=2000)
            faults.clear_plan()
            assert eng.metrics.completed == len(prompts)
            return eng, [out[r].tokens for r in rids]

        _, want = run(None)
        eng, got = run(
            [{"point": "serve.prefix_attach", "action": "reset",
              "after": 2}]
        )
        assert eng.metrics.requeued >= 1
        assert got == want
        # shared blocks stayed intact through the fault: later requests
        # still hit the cached preamble
        assert eng.metrics.prefix_hits > 0
        assert eng.cache.live_blocks == 0

    def test_prefix_attach_fault_point_is_registered(self):
        assert "serve.prefix_attach" in faults.KNOWN_POINTS


class TestTenantIsolation:
    def _run_two_tenants(self, share_a, share_b, seed0=0):
        """Tenant t1 (class a) runs first and populates whatever scope
        it writes to; tenant t2 (class b) with the IDENTICAL preamble
        runs after. Returns t2's engine-level hit count + tokens."""
        from pytorch_distributed_example_tpu.serve import (
            ClassSpec,
            ServeEngine,
        )

        model, params = _model()
        _, prompts = _preamble_prompts(14, [5, 4], seed=6)
        classes = {
            "a": ClassSpec(priority=0, share_prefix=share_a),
            "b": ClassSpec(priority=0, share_prefix=share_b),
        }
        eng = ServeEngine(
            model, params, slots=2, min_bucket=4,
            prefill_chunk_tokens=6, block_size=4, prefix_cache=True,
            classes=classes,
        )
        r1 = eng.submit(prompts[0], 5, tenant="t1", klass="a",
                        seed=seed0)
        eng.run(max_steps=800)
        hits_before = eng.metrics.prefix_hits
        r2 = eng.submit(prompts[1], 5, tenant="t2", klass="b",
                        seed=seed0 + 1)
        out = eng.run(max_steps=800)
        return eng.metrics.prefix_hits - hits_before, out[r2].tokens

    def test_no_sharing_unless_both_opt_in(self, no_fault_plan):
        """SATELLITE: identical preambles across tenants share nothing
        by default, nor when only ONE side opts in."""
        for sa, sb in [(False, False), (True, False), (False, True)]:
            hits, _ = self._run_two_tenants(sa, sb)
            assert hits == 0, f"leak with share_prefix=({sa}, {sb})"

    def test_opted_in_sharing_hits_without_leaking_tokens(
        self, no_fault_plan
    ):
        """Both classes opted in: t2 hits t1's preamble, and its served
        tokens are IDENTICAL to the fully isolated run — shared state
        never changes (or leaks into) what t2 is served."""
        hits_shared, toks_shared = self._run_two_tenants(True, True)
        hits_iso, toks_iso = self._run_two_tenants(False, False)
        assert hits_shared >= 1 and hits_iso == 0
        assert toks_shared == toks_iso

    def test_same_tenant_shares_without_opt_in(self, no_fault_plan):
        """The default scope is PER-TENANT, not per-request: one
        tenant's identical preambles share freely."""
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        _, prompts = _preamble_prompts(14, [5, 4], seed=7)
        eng = ServeEngine(
            model, params, slots=2, min_bucket=4,
            prefill_chunk_tokens=6, block_size=4, prefix_cache=True,
        )
        eng.submit(prompts[0], 4, tenant="t1")
        eng.run(max_steps=800)
        eng.submit(prompts[1], 4, tenant="t1")
        eng.run(max_steps=800)
        assert eng.metrics.prefix_hits == 1


class TestPrefixMetrics:
    def test_serve_route_reports_prefix_cache(self, no_fault_plan):
        """SATELLITE: /serve exposes the prefix_cache block — hit rate,
        tokens reused, shared/CoW counts, bytes deduplicated."""
        from pytorch_distributed_example_tpu.serve import ServeEngine
        from pytorch_distributed_example_tpu.utils.debug_http import (
            DebugServer,
        )

        model, params = _model()
        pre, prompts = _preamble_prompts(14, [4, 5, 3], seed=8)
        eng = ServeEngine(
            model, params, slots=2, min_bucket=4,
            prefill_chunk_tokens=6, block_size=4, prefix_cache=True,
        )
        # warm request leaves the preamble resident, then a concurrent
        # burst shares it (refcount > 1 -> dedup bytes observable)
        eng.submit(np.concatenate([pre, np.asarray([1], np.int32)]), 2)
        eng.run(max_steps=400)
        for p in prompts:
            eng.submit(p, 4)
        eng.run(max_steps=1200)
        srv = DebugServer()
        try:
            srv.register_serve_metrics("engine", eng.metrics)
            with urllib.request.urlopen(srv.url + "/serve") as r:
                doc = json.loads(r.read())
            pc = doc["engine"]["prefix_cache"]
            assert pc["hits"] >= 1
            assert 0.0 < pc["hit_rate"] <= 1.0
            assert pc["prefix_tokens_reused"] > 0
            assert pc["cow_copies"] >= 1
            assert "shared_blocks" in pc and "cached_blocks" in pc
            assert "bytes_deduplicated" in pc
            assert pc["peak_bytes_deduplicated"] > 0
        finally:
            srv.shutdown()

    def test_prefix_block_present_and_zero_when_off(self, no_fault_plan):
        from pytorch_distributed_example_tpu.serve import ServeEngine

        model, params = _model()
        _, prompts = _preamble_prompts(10, [4], seed=9)
        eng = ServeEngine(model, params, slots=1, min_bucket=4)
        eng.submit(prompts[0], 3)
        eng.run(max_steps=200)
        pc = eng.metrics.snapshot()["prefix_cache"]
        assert pc["hits"] == 0 and pc["misses"] == 0
        assert pc["cow_copies"] == 0 and pc["bytes_deduplicated"] == 0


class TestSharedDecode:
    """Several rows decoding TOGETHER over one head, at a head size the
    decode kernel takes (Dh = 128, float32, interpreted here): the kernel
    reads the head's whole compute block once for the group
    (`ops/paged_attention.py::shared_runs`), and nothing a request sees
    changes. The benchmark's check cannot see this case (its one row
    attaches to a retired request's blocks: a group of one)."""

    HEAD, BS = 272, 16  # 17 pages: one whole 256-key block and a page

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
            n_kv_heads=2, d_ff=64, max_seq_len=320, use_flash=False,
        )
        model = TransformerLM(cfg)
        return model, model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )

    def _serve(self, model, params, prompts, prefix_cache, between=None):
        """The eight requests through one engine: the first alone until it
        decodes (its head is indexed by then), then the other seven, so
        all eight decode together. Returns (tokens a request, logits a
        (request, position), the engine, every decode call's record)."""
        import jax

        from pytorch_distributed_example_tpu.serve import ServeEngine

        eng = ServeEngine(
            model, params, slots=8, min_bucket=16, block_size=self.BS,
            prefill_chunk_tokens=64, prefix_cache=prefix_cache,
        )
        assert eng.metrics.snapshot()["decode"]["layer_paths"]
        logits_of = jax.jit(lambda params, tree, tokens, lengths, bt: model.apply(
            {"params": params, "cache": tree}, tokens[:, None], decode=True,
            positions=lengths, block_tables=bt, mutable=["cache"],
        )[0][:, -1])
        program, seen = eng._step, {}

        def probe(params, tree, lengths, tokens, rngs, bt):
            # the step's operands through every layer's `_decode_paged`,
            # before the step takes (and donates) them
            rows = np.asarray(logits_of(params, tree, tokens, lengths, bt))
            at = np.asarray(lengths)
            for s in eng._decoding:
                seen[(eng._slot_req[s].rid, int(at[s]))] = rows[s]
            return program(params, tree, lengths, tokens, rngs, bt)

        eng._step = probe
        rids = [eng.submit(prompts[0], 16)]
        records = []
        for _ in range(200):
            eng.step()
            if eng.metrics.decode_steps:
                break
        rids += [eng.submit(p, 6) for p in prompts[1:]]
        for _ in range(400):
            busy = eng.step()
            if eng.last_step.decode_keys:
                records.append(eng.last_step)
                if between is not None:
                    between(eng, rids)
            if not busy:
                break
        assert eng.metrics.completed == len(prompts)
        done = eng.completions
        tokens = [list(map(int, done[r].tokens)) for r in rids]
        # by the request's place among the eight: an engine names its own
        seen = {(rids.index(r), at): row for (r, at), row in seen.items()}
        return tokens, seen, eng, records

    def test_eight_rows_over_one_head_decode_as_without_sharing(self, no_fault_plan):
        model, params = self._wide_model()
        _, prompts = _preamble_prompts(self.HEAD, [3, 9, 20, 5, 14, 7, 11, 17], seed=3)
        cowed = {}

        def cow_one(eng, rids):
            """Once all eight decode together: request 3 takes a private
            copy of the head's first page (what a write into it would
            force), and leaves the group for that block."""
            from pytorch_distributed_example_tpu.ops import paged_attention

            if cowed or len(eng._decoding) < 8:
                return
            slot = next(s for s in eng._decoding if eng._slot_req[s].rid == rids[3])
            tables = lambda: eng.cache.tables()
            shared = lambda: paged_attention.shared_decode_keys(
                tables(), eng.cache.lengths, eng.cache.invalid_block, self.BS)
            before, page = shared(), int(tables()[slot, 0])
            assert eng.cache.cow_block(slot, 5)
            assert int(tables()[slot, 0]) != page
            cowed.update(before=before, after=shared(), slot=slot)

        shared_tokens, shared_logits, eng, records = self._serve(
            model, params, prompts, True, between=cow_one)
        plain_tokens, plain_logits, plain, plain_records = self._serve(
            model, params, prompts, False)
        assert eng.metrics.snapshot()["decode"]["kernel_share"] == 1.0
        assert shared_tokens == plain_tokens
        # the other seven attached the head: its whole block is theirs and
        # the first request's at once, read once a step for all eight
        assert eng.metrics.snapshot()["prefix_cache"]["hits"] == 7
        assert max(r.decode_shared_keys for r in records) == 8 * 256
        assert cowed["before"] == 8 * 256 and cowed["after"] == 7 * 256
        assert all(r.decode_shared_keys == 0 for r in plain_records)
        both = sorted(set(shared_logits) & set(plain_logits))
        assert len(both) >= sum(map(len, shared_tokens)) - 16
        worst = max(
            np.abs(shared_logits[k] - plain_logits[k]).max() for k in both
        )
        assert worst <= 2e-5, worst
        # steps after the copy: seven rows share, the eighth reads its own
        after = [r for r in records if r.decode_shared_keys == 7 * 256]
        assert after and eng.cache.cow_copies >= 1
