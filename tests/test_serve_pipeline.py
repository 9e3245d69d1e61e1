"""`ServeEngine.step` keeps one call's device work in flight: a call
dispatches its own programs, then reads back what the previous call
dispatched. What that must not change (the token streams, against
`generate()` and against the streams the engine gave before it), and what
it brings: the order of events, an EOS in flight, eviction and faults over
an outstanding result, the seams that read everything back first, and what
the benchmark's runner reads off the engine between calls.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

from pytorch_distributed_example_tpu import faults
from tests import _serve_toys as toys

HERE = Path(__file__).resolve().parent
RECORDED = json.loads((HERE / "fixtures" / "serve_pipeline_streams.json").read_text())


@pytest.fixture(scope="module")
def built():
    return {}


def toy(built, kind):
    if kind not in built:
        built[kind] = toys.build(kind)
    return built[kind]


@pytest.fixture()
def no_fault_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


def reference(model, variables, prompt, budget, eos_id=None):
    """`generate()`'s greedy tokens for one request."""
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.models.generate import generate

    out = generate(model, variables, jnp.asarray(prompt)[None], budget, eos_id=eos_id)
    return [int(t) for t in np.asarray(out)[0]]


# --- (a) the streams are the ones the engine gave before ---------------------

@pytest.mark.parametrize("chunking", list(toys.CHUNKING))
@pytest.mark.parametrize("sampling", list(toys.SAMPLING))
@pytest.mark.parametrize("kind", toys.KINDS)
def test_streams_are_the_parent_s_and_generate_s(built, kind, sampling, chunking):
    """Five requests on three slots (a budget of one, a prompt of three
    chunks, slots that change hands), token for token what the engine gave
    at the commit before the lag, and what `generate()` gives alone."""
    model, variables, vocab = toy(built, kind)
    got = toys.serve(model, variables, vocab, sampling, chunking)
    assert got == RECORDED[f"{kind}/{sampling}/{chunking}"]
    if sampling == "seeded":
        # the engine's stream of keys a request is its own, not `generate()`'s
        # batch's: alone on one slot a request draws what it drew in company
        assert got == toys.serve(model, variables, vocab, sampling, chunking, slots=1)
        return
    for rid, prompt, budget, seed in toys.requests(vocab):
        assert got[rid] == reference(model, variables, prompt, budget), rid


# --- (b) the order of events ---------------------------------------------------

class Events:
    """Every dispatch of the engine's four programs and every host read of
    a device array (`np.asarray` in the engine's module, which numpy serves
    through the buffer protocol, and `int()`, `.item()`, `.tolist()`, which
    go through the array's `_value`), in order, each with the `step()` call
    it happened in."""

    def __init__(self, engine, monkeypatch):
        import types

        import jax
        from jax._src import array as jax_array

        from pytorch_distributed_example_tpu.serve import engine as engine_module

        self.log, self.call, self.made = [], 0, {}
        for name in ("_prefill_chunk", "_first_token", "_attach", "_step"):
            setattr(engine, name, self._dispatch(name, getattr(engine, name)))
        value = jax_array.ArrayImpl._value

        def read(arr):
            self.log.append(("read", self.call, self.made.get(id(arr))))
            return value.fget(arr)

        def asarray(x, *args, **kw):
            if isinstance(x, jax.Array):
                self.log.append(("read", self.call, self.made.get(id(x))))
            return np.asarray(x, *args, **kw)

        monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(read))
        monkeypatch.setattr(engine_module, "np", types.SimpleNamespace(
            **{**vars(np), "asarray": asarray}))
        self.engine = engine

    def _dispatch(self, name, program):
        def run(*args):
            out = program(*args)
            self.log.append((name, self.call, None))
            if name == "_step":
                self.made[id(out[4])] = ("tokens", self.call)
            if name == "_first_token":
                self.made[id(out[0])] = ("first", self.call)
            return out
        return run

    def step(self):
        self.call += 1
        return self.engine.step()


def test_a_call_dispatches_before_it_reads_and_reads_the_previous_call_s(
        built, monkeypatch):
    model, variables, vocab = toy(built, "dense")
    engine = toys.engine_of(model, variables, "greedy", "chunked")
    for rid, prompt, budget, seed in toys.requests(vocab):
        engine.submit(prompt, budget, rid=rid, seed=seed)
    events = Events(engine, monkeypatch)
    while events.step():
        assert events.call < 200
    log = events.log
    reads = [e for e in log if e[0] == "read"]
    assert len(reads) > 20 and all(what is not None for _, _, what in reads), reads
    for call in range(1, events.call + 1):
        mine = [kind for kind, c, _ in log if c == call]
        if "read" in mine:
            # no host read of a device value between two dispatches of a call
            assert all(kind == "read" for kind in mine[mine.index("read"):]), (call, mine)
    # what a call reads is what the call before it dispatched: a finished
    # prefill's first token before the tokens of the decode step behind it
    for _, call, (what, made_in) in reads:
        assert made_in == call - 1, (what, made_in, call)
    for call in range(2, events.call + 1):
        kinds = [what for _, c, (what, _) in reads if c == call]
        assert kinds == sorted(kinds), (call, kinds)  # "first" < "tokens"
    pipeline = engine.metrics.snapshot()["pipeline"]
    steps = sum(1 for kind, _, _ in log if kind == "_step")
    assert steps == engine.metrics.decode_steps
    # a decode step ran ahead of a read wherever the call before it had
    # dispatched a result: all of them here but for the ends of the run
    left_a_result = {c for kind, c, _ in log if kind in ("_first_token", "_step")}
    ahead = sum(1 for kind, c, _ in log if kind == "_step" and c - 1 in left_a_result)
    assert steps - 2 <= ahead <= steps
    assert pipeline["overlap_share"] == pytest.approx(ahead / steps, abs=1e-4)
    assert pipeline["flushes"] == {"idle": 1}  # the call that ends `run()`


# --- (c) an EOS in flight --------------------------------------------------------

def first_repeat_free(stream, lo):
    """An index >= lo whose token does not occur earlier in the stream."""
    return next(j for j in range(lo, len(stream)) if stream[j] not in stream[:j])


@pytest.mark.parametrize("where", ["decoded", "first"])
def test_an_eos_in_flight_costs_one_dropped_lane(built, where):
    """The row whose EOS the host has not seen yet is in the next dispatch
    too: that token is dropped, the stream ends at the EOS, and the row's
    slot and blocks are freed once (a second `free` raises)."""
    model, variables, vocab = toy(built, "dense")
    (_, prompt, _, seed), (_, other, _, seed2) = toys.requests(vocab)[:2]
    full = reference(model, variables, prompt, 12)
    j = 0 if where == "first" else first_repeat_free(full, 2)
    engine = toys.engine_of(model, variables, "greedy", "chunked", eos_id=full[j])
    lanes = []
    program = engine._step
    engine._step = lambda *a: lanes.append(sorted(engine._decoding)) or program(*a)
    engine.submit(prompt, 12, rid="ends", seed=seed)
    engine.submit(other, 12, rid="beside", seed=seed2)
    done = engine.run(max_steps=200)
    assert done["ends"].tokens == full[:j + 1] and done["ends"].finish_reason == "eos"
    want = reference(model, variables, other, 12, eos_id=full[j])
    cut = want.index(full[j]) + 1 if full[j] in want else 12
    assert done["beside"].tokens == want[:cut]
    # tokens 2..j+1 take j decode lanes; one more was in flight behind the
    # EOS, and behind a FIRST token two: the step of the call that finished
    # the prefill and the next call's, which is dispatched before the read
    slot = 0
    assert sum(slot in step for step in lanes) == (j + 1 if j else 2)
    assert engine.cache.live_blocks == 0 and not engine.cache.active_slots
    assert engine.cache.free_blocks == engine.cache.num_blocks


def test_a_prefill_pool_s_eos_first_token_is_no_handoff(built):
    model, variables, vocab = toy(built, "dense")
    (_, prompt, _, seed), (_, other, _, seed2) = toys.requests(vocab)[:2]
    first = reference(model, variables, prompt, 1)[0]
    engine = toys.engine_of(model, variables, "greedy", "chunked", eos_id=first,
                            role="prefill")
    engine.submit(prompt, 8, rid="ends", seed=seed)
    engine.submit(other, 8, rid="moves", seed=seed2)
    while engine.step():
        pass
    handoffs = engine.pop_handoffs()
    assert [h.req.rid for h in handoffs] == ["moves"]
    assert handoffs[0].first == reference(model, variables, other, 1)[0]
    assert engine.completions["ends"].tokens == [first]
    assert engine.completions["ends"].finish_reason == "eos"


# --- (d) eviction, requeue and a fault over an outstanding result ----------------

def workload(built, kind="dense", sampling="greedy"):
    """A toy and the streams the engine gave for the workload before."""
    model, variables, vocab = toy(built, kind)
    return model, variables, vocab, RECORDED[f"{kind}/{sampling}/chunked"]


def submit_all(engine, vocab):
    for rid, prompt, budget, seed in toys.requests(vocab):
        engine.submit(prompt, budget, rid=rid, seed=seed)


@pytest.mark.parametrize("tenant", ["another", "itself"])
def test_an_evicted_row_s_token_is_not_the_next_tenant_s(built, tenant):
    """A decoding row is evicted while its token is in flight and its slot
    is taken in the next call, by another request or by the evictee itself
    from the queue's head: the token is dropped (the new tenancy starts
    empty) and every stream, the evictee's replay too, is exact."""
    model, variables, vocab, want = workload(built)
    engine = toys.engine_of(model, variables, "greedy", "chunked")
    submit_all(engine, vocab)
    for _ in range(3):
        engine.step()
    assert len(engine.cache.active_slots) == 3 and engine.queue.depth >= 1
    slot = min(engine._decoding)
    evicted = engine._slot_req[slot]
    assert any(slot == s for res in engine._inflight for s, _ in res.rows)
    engine._evict(slot, requeue_counter=False)
    if tenant == "another":
        assert engine.queue.pop_specific(evicted)  # let the next in line in
    engine.step()  # admits into the one free slot, reads the old row's token
    assert engine._slot_req[slot] is not None
    assert (engine._slot_req[slot] is evicted) == (tenant == "itself")
    assert engine._slot_tokens[slot] == []
    if tenant == "another":
        engine.queue.requeue_front(evicted)
    done = engine.run(max_steps=300)
    assert {rid: c.tokens for rid, c in done.items()} == want
    assert done[evicted.rid].requeues == 1


@pytest.mark.parametrize("sampling", list(toys.SAMPLING))
def test_requeue_inflight_reads_back_first_and_replays_exactly(built, sampling):
    model, variables, vocab, want = workload(built, sampling=sampling)
    engine = toys.engine_of(model, variables, sampling, "chunked")
    submit_all(engine, vocab)
    for _ in range(4):
        engine.step()
    assert engine._inflight
    before = len(engine.completions)
    n = engine.requeue_inflight()
    assert not engine._inflight and engine.num_active == 0 and n >= 1
    assert engine.metrics.snapshot()["pipeline"]["flushes"]["requeue"] == 1
    # what the read-back finished is done, not replayed
    assert len(engine.completions) >= before
    assert all(c.requeues == 0 for c in engine.completions.values())
    done = engine.run(max_steps=300)
    assert {rid: c.tokens for rid, c in done.items()} == want


@pytest.mark.parametrize("kind", toys.KINDS)
def test_a_step_fault_over_an_outstanding_result_replays_exactly(
        built, no_fault_plan, kind):
    model, variables, vocab, want = workload(built, kind)
    engine = toys.engine_of(model, variables, "greedy", "chunked")
    submit_all(engine, vocab)
    faults.install_plan(
        [{"point": "serve.step", "action": "reset", "after": 3, "times": 1}],
        export_env=False)
    done = engine.run(max_steps=400)
    assert engine.metrics.requeued >= 1
    assert {rid: c.tokens for rid, c in done.items()} == want


def test_pool_pressure_over_outstanding_results_replays_exactly(built):
    """A pool of one longest request under three that grow to most of it:
    growth preempts rows whose tokens are in flight, call after call."""
    model, variables, vocab = toy(built, "dense")
    engine = toys.engine_of(model, variables, "greedy", "chunked", pool_blocks=toys.M // toys.BS)
    asked = [(rid, prompt, 60) for rid, prompt, _, _ in toys.requests(vocab)[:3]]
    for rid, prompt, budget in asked:
        engine.submit(prompt, budget, rid=rid)
    done = engine.run(max_steps=3000)
    assert engine.metrics.preempted > 0
    for rid, prompt, budget in asked:
        assert done[rid].tokens == reference(model, variables, prompt, budget), rid


# --- (e) the seams read everything back first ------------------------------------

def lockstep(built, calls):
    """Two engines over three long requests, `calls` calls each: one as it
    is, one read back after every call (what a synchronous engine holds)."""
    model, variables, vocab = toy(built, "dense")
    pair = []
    for sync in (False, True):
        engine = toys.engine_of(model, variables, "greedy", "chunked")
        for rid, prompt, _, seed in toys.requests(vocab)[:3]:
            engine.submit(prompt, 20, rid=rid, seed=seed, arrival_time=1.0)
        for _ in range(calls):
            engine.step()
            if sync:
                engine.flush()
        pair.append(engine)
    return pair


def state_of(snapshot):
    return {k: v for k, v in snapshot.items() if k != "checkpoint_time"}


@pytest.mark.parametrize("seam", ["snapshot_state", "drain"])
def test_a_snapshot_is_a_synchronous_engine_s(built, seam):
    lagging, sync = lockstep(built, 5)
    assert lagging._inflight and not sync._inflight
    held = {r.rid: len(t) for r, t in zip(lagging._slot_req, lagging._slot_tokens) if r}
    got, want = getattr(lagging, seam)(), getattr(sync, seam)()
    assert state_of(got) == state_of(want)
    # every token dispatched is in the ledger: more than the host held before
    assert sum(got["emitted"].values()) > sum(held.values())
    cause = seam.split("_")[0]
    assert lagging.metrics.snapshot()["pipeline"]["flushes"] == {cause: 1}
    assert not lagging._inflight


def test_a_snapshot_fault_leaves_the_engine_untouched(built, no_fault_plan):
    (lagging, _) = lockstep(built, 3)
    outstanding = len(lagging._inflight)
    faults.install_plan([{"point": "serve.drain", "action": "reset", "times": 1}],
                        export_env=False)
    with pytest.raises(ConnectionResetError):
        lagging.snapshot_state()
    assert len(lagging._inflight) == outstanding
    assert lagging.metrics.snapshot()["pipeline"]["flushes"] == {}


def test_handoffs_are_handed_out_with_their_first_token(built):
    model, variables, vocab = toy(built, "dense")
    engine = toys.engine_of(model, variables, "greedy", "unchunked", role="prefill")
    asked = toys.requests(vocab)[:2]
    for rid, prompt, _, seed in asked:
        engine.submit(prompt, 8, rid=rid, seed=seed)
    engine.step()  # both prefills dispatched; neither first token read
    assert [h.first for h in engine._handoff] == [None, None]
    assert all(r.first_token_time is None for r in engine._slot_req if r)
    handoffs = engine.pop_handoffs()
    assert {h.req.rid: h.first for h in handoffs} == {
        rid: reference(model, variables, prompt, 1)[0] for rid, prompt, _, _ in asked}
    assert all(h.req.first_token_time is not None for h in handoffs)
    assert engine.metrics.snapshot()["pipeline"]["flushes"] == {"handoff": 1}
    for h in handoffs:
        engine.release_handoff(h)
    assert not engine.cache.active_slots


# --- (f) what the benchmark's runner reads off the engine ------------------------

@pytest.fixture(scope="module")
def runner_loop():
    """The runner's own loop and probe (`bench_matrix/runners/serve.py`)
    over the tiny preset of `serve_decode_c32`, closed loop of four clients
    on four slots, until 24 requests are done and the engine is empty."""
    sys.path.insert(0, str(HERE / "bench_matrix"))
    from _tiny import context, tiny_cell
    from bench_matrix import modelglue
    from bench_matrix.runners.serve import _DecodeProbe, _Loop
    from pytorch_distributed_example_tpu.serve import ServeEngine

    cell = tiny_cell("serve_decode_c32")
    config, traffic = cell["config"], cell["traffic"]
    ctx = context(1.0, [])
    ctx.compiles.close()
    eng = dict(traffic["engine"])
    model = modelglue.build_model(config, eng.pop("max_seq_len"), remat=False)
    engine = ServeEngine(model, modelglue.make_variables(model, config, 5),
                         clock=ctx.clock, **eng)
    seen = {"dispatch": [], "after": []}
    program = engine._step

    def checked(params, tree, lengths, tokens, rngs, bt):
        seen["dispatch"].append({
            "decoding": sorted(engine._decoding),
            "host": engine.cache.lengths.copy(), "device": np.asarray(lengths),
            "live_rows": np.flatnonzero((bt < engine.cache.invalid_block).any(axis=1)).tolist(),
        })
        return program(params, tree, lengths, tokens, rngs, bt)

    engine._step = checked
    probe = engine._step = _DecodeProbe(engine)
    loop = _Loop(engine, traffic, config["vocab_size"], ctx)
    loop.start(60.0)
    while len(loop.done) < 24 or engine.num_active or engine._inflight:
        if len(loop.done) >= 24:
            loop.closed = False  # stop resubmitting: let the engine empty
            loop.pending = []
        loop.step()
        seen["after"].append({
            "lengths": engine.cache.lengths.copy(),
            "prefilling": sorted(engine._prefilling),
            "held": {s: (r.rid, len(r.prompt), r.max_new_tokens,
                         len(engine._slot_tokens[s]))
                     for s, r in enumerate(engine._slot_req) if r is not None},
        })
        assert len(loop.step_end) < 2000
    return {"engine": engine, "loop": loop, "probe": probe, "seen": seen}


def test_the_probe_sees_the_set_a_step_decodes_at_its_depth(runner_loop):
    probe, seen = runner_loop["probe"], runner_loop["seen"]
    assert len(probe.keys) == len(seen["dispatch"]) > 50
    for keys, at in zip(probe.keys, seen["dispatch"]):
        rows = at["decoding"]
        # the decoding set IS what the call decodes: every other lane is
        # parked, and the host's mirror is the device's depth before the write
        assert rows == at["live_rows"] and rows
        assert at["host"][rows].tolist() == at["device"][rows].tolist()
        assert keys == [int(at["host"][s]) + 1 for s in rows]


def test_lengths_after_a_call_are_what_the_runner_counts_from(runner_loop):
    """0 for a free or prefilling slot, prompt + 1 in the call whose chunk
    finished the prefill, one more per decode dispatch; so the runner's
    count of tokens, made from nothing else, ends at the tokens served."""
    loop, seen = runner_loop["loop"], runner_loop["seen"]
    before = None
    for now in seen["after"]:
        for s, length in enumerate(now["lengths"]):
            if s not in now["held"] or s in now["prefilling"]:
                assert length == 0
                continue
            rid, prompt, budget, _ = now["held"][s]
            same = before is not None and before["held"].get(s, (None,))[0] == rid
            was = int(before["lengths"][s]) if same else 0
            if was == 0:
                assert length == prompt + 1  # first token, and this call's decode
            else:
                assert length in (was, was + 1)
                # it stops where the budget is spent, one call before its last
                # token is read: never past it
                assert length - prompt + 1 <= budget
                if length == was:
                    assert length - prompt + 1 == budget
        before = now
    done = loop.done
    assert loop.progress["all"][-1] == sum(d["prompt"] + d["tokens"] for d in done)
    assert loop.progress["generated"][-1] == sum(d["tokens"] for d in done)
    for series in loop.progress.values():
        assert all(b >= a for a, b in zip(series, series[1:]))


def test_a_request_s_tokens_come_in_consecutive_calls_ending_at_its_last(runner_loop):
    """What `inter_token_s` assumes: a request that completes in call j with
    n tokens got tokens 2..n in the n - 1 consecutive calls ending at j."""
    from bench_matrix.runners.serve import inter_token_s

    loop, seen = runner_loop["loop"], runner_loop["seen"]
    checked = 0
    for d in loop.done:
        if d["requeues"] or d["tokens"] < 2:
            continue
        n, j = d["tokens"], d["step"]
        first = j - (n - 2)  # the call that gave token 2
        # the request, by what it held in the call before its last: all but
        # the last of its tokens (none yet, where it got both in call j)
        mine = [rid for rid, prompt, budget, count in seen["after"][j - 1]["held"].values()
                if (prompt, budget, count) == (d["prompt"], n, n - 1 if n > 2 else 0)
                and rid not in {v[0] for v in seen["after"][j]["held"].values()}]
        assert len(mine) >= 1, d
        if len(mine) > 1:
            continue  # two of a kind retired together: cannot tell them apart
        counts = {call: {v[0]: v[3] for v in seen["after"][call]["held"].values()}.get(mine[0])
                  for call in range(first - 1, j)}
        assert counts[first - 1] == 0  # its first token was still on the device
        assert [counts[c] for c in range(first, j)] == list(range(2, n)), (d, counts)
        checked += 1
    assert checked >= 15
    gaps, skipped = inter_token_s(
        [d for d in loop.done if d["reason"] == "length"], loop.step_end)
    assert skipped == 0 and (gaps > 0).all()
    assert len(gaps) == sum(d["tokens"] - 1 for d in loop.done if d["tokens"] > 1)


# --- a profiler trace: nothing in the engine looks for one --------------------------

def traced(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)


def test_a_trace_and_a_wrapped_step_leave_the_pipeline_as_it_is(built, tmp_path):
    """The engine a profiler sees is the engine that serves: a trace that
    records, and a wrapper put on `_step` to watch it (the runner's probe),
    read nothing back."""
    import jax

    model, variables, vocab = toy(built, "dense")
    engine = toys.engine_of(model, variables, "greedy", "chunked")
    for rid, prompt, _, seed in toys.requests(vocab)[:3]:
        engine.submit(prompt, 20, rid=rid, seed=seed)
    for _ in range(3):
        engine.step()
    program, calls = engine._step, []
    engine._step = lambda *a: calls.append(len(engine._inflight)) or program(*a)
    traced(tmp_path)
    try:
        for _ in range(4):
            engine.step()
            assert engine._inflight
    finally:
        jax.profiler.stop_trace()
        engine._step = program
    # each dispatched over what the call before it left unread
    assert len(calls) == 4 and min(calls) >= 1
    assert engine.metrics.pipeline_flushes == {}
    done = engine.run(max_steps=200)
    for rid, prompt, _, _ in toys.requests(vocab)[:3]:
        assert done[rid].tokens == reference(model, variables, prompt, 20), rid


def test_a_caller_that_flushes_around_its_trace_gets_whole_steps(built, tmp_path):
    """`stop_trace` keeps only what ran before it, so whoever pairs a
    slice's decode steps with what it watched calls `flush()` before the
    trace starts and before it stops: then every step it saw dispatched was
    read back inside the slice (one `serve:moe_step` on the host line each)."""
    import jax
    from jax.profiler import ProfileData

    from bench_matrix.reduce import xplane

    model, variables, vocab = toy(built, "sparse_window")
    engine = toys.engine_of(model, variables, "greedy", "chunked")
    for rid, prompt, _, seed in toys.requests(vocab)[:3]:
        engine.submit(prompt, 20, rid=rid, seed=seed)
    for _ in range(4):
        engine.step()
    assert engine._inflight
    program, seen = engine._step, []
    engine._step = lambda *a: seen.append(1) or program(*a)
    engine.flush()
    traced(tmp_path)
    try:
        for _ in range(5):
            engine.step()
        engine.flush()
    finally:
        jax.profiler.stop_trace()
        engine._step = program
    assert not engine._inflight
    assert engine.metrics.pipeline_flushes == {"caller": 2}
    noted = [ev.name for plane in ProfileData.from_file(xplane.find(str(tmp_path))).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.split("#")[0] == "serve:moe_step"]
    assert len(noted) == len(seen) == 5
    engine.step()
    assert engine._inflight  # and the pipeline is back with the next call
