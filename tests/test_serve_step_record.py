"""`ServeEngine.step` says what it did: one `StepRecord` a call, public as
`engine.last_step`, and the same record on the host line of a profiler
trace. What must hold: the record agrees with what the benchmark's two
probes keep (so the runner can read it instead), its counts add up over a
run whatever interrupts it, its phases fit inside the call, a request's
stamps are one a token and its replay's own, and nothing is different with
a trace on.
"""

import dataclasses
import json
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from pytorch_distributed_example_tpu import faults
from pytorch_distributed_example_tpu.serve import PHASES, StepRecord
from tests import _serve_toys as toys

RECORDED = json.loads(
    (Path(__file__).resolve().parent / "fixtures" / "serve_pipeline_streams.json").read_text())


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


class Ticks:
    """A clock that advances a millisecond a read: what a record times is
    then the number of reads, the same traced and untraced."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def submit_all(engine, vocab):
    for rid, prompt, budget, seed in toys.requests(vocab):
        engine.submit(prompt, budget, rid=rid, seed=seed)


def drive(engine, limit=3000):
    """Step until nothing is left; every call's record, in order."""
    records = []
    while True:
        busy = engine.step()
        records.append(engine.last_step)
        assert len(records) < limit
        if not busy:
            return records


def stamps_view(engine, window_s=1e6):
    """`window.host`, `window.queue_ms`, `window.itl_ms` of the operator's
    page over the trailing `window_s` seconds."""
    engine.metrics.window_s = window_s
    return engine.metrics.snapshot()["window"]


def want_of(kind):
    return RECORDED[f"{kind}/greedy/chunked"]


def tokens_of(done):
    return {rid: [int(t) for t in c.tokens] for rid, c in done.items()}


# --- the record against the runner's probes ------------------------------------

class KeepingPrefillProbe:
    """The runner's `_PrefillProbe`, keeping every call's (start, bucket)
    where the probe keeps the last."""

    def __init__(self, program):
        from bench_matrix.runners.serve import _PrefillProbe

        self.probe, self.all = _PrefillProbe(program), []

    def __call__(self, params, tree, chunk, bt_row, start):
        out = self.probe(params, tree, chunk, bt_row, start)
        self.all.append((self.probe.last[0], int(chunk.shape[1])))
        return out


@pytest.mark.parametrize("case", ["dense", "sparse_window", "linear", "dense_prefix_cache"])
def test_the_record_says_what_the_runner_s_probes_keep_call_for_call(built, case):
    """`chunks` and `decode_keys` of every call against a `_PrefillProbe` /
    `_DecodeProbe` pair wrapped round the same engine: the runner can read
    `engine.last_step` where it replaces the engine's programs today."""
    from bench_matrix.runners.serve import _DecodeProbe

    kind = case.split("_prefix")[0]
    model, variables, vocab = toy(built, kind)
    engine = toys.engine_of(model, variables, "greedy", "chunked",
                            prefix_cache=case.endswith("prefix_cache"))
    chunks = engine._prefill_chunk = KeepingPrefillProbe(engine._prefill_chunk)
    steps = engine._step = _DecodeProbe(engine)
    submit_all(engine, vocab)
    seen_chunks = seen_steps = 0
    where = {}  # slot -> the next position its prefill starts at
    while True:
        busy = engine.step()
        rec = engine.last_step
        assert [(start, bucket) for _, start, _, bucket in rec.chunks] == chunks.all[seen_chunks:]
        seen_chunks = len(chunks.all)
        new = steps.keys[seen_steps:]
        assert len(new) <= 1
        assert list(rec.decode_keys) == (new[0] if new else [])
        assert rec.decode_rows == len(rec.decode_keys)
        seen_steps = len(steps.keys)
        for slot, start, tokens, bucket in rec.chunks:
            # a slot's chunks follow each other; a chunk fits its bucket
            assert 1 <= tokens <= bucket
            assert where.get(slot, start) == start
            where[slot] = start + tokens
            if engine._slot_req[slot] is None or slot not in engine._prefilling:
                where.pop(slot)
        if not busy:
            break
    assert seen_chunks == engine.metrics.prefill_chunks > 5
    assert seen_steps == engine.metrics.decode_steps > 5
    assert tokens_of(engine.completions) == want_of(kind)


# --- counts over a run, whatever interrupts it -----------------------------------

def counting_first_tokens(engine):
    """The engine with its first-token program counted: [1] a dispatch."""
    program, firsts = engine._first_token, []
    engine._first_token = lambda *a: firsts.append(1) or program(*a)
    return firsts


def interrupted(built, scenario):
    """(engine, records, first tokens dispatched, expected streams or None)
    of one run."""
    model, variables, vocab = toy(built, "dense")
    if scenario == "pool_pressure":
        engine = toys.engine_of(model, variables, "greedy", "chunked",
                                pool_blocks=toys.M // toys.BS)
        firsts = counting_first_tokens(engine)
        for rid, prompt, _, _ in toys.requests(vocab)[:3]:
            engine.submit(prompt, 60, rid=rid)
        return engine, drive(engine), firsts, None
    if scenario == "class_preemption":
        from pytorch_distributed_example_tpu.serve import ClassSpec

        engine = toys.engine_of(
            model, variables, "greedy", "chunked",
            classes={"gold": ClassSpec(priority=0, weight=4), "bronze": ClassSpec(priority=1)})
        firsts = counting_first_tokens(engine)
        reqs = toys.requests(vocab)
        for rid, prompt, _, seed in reqs[:3]:
            engine.submit(prompt, 20, rid=rid, seed=seed, klass="bronze")
        records = []
        for _ in range(3):
            engine.step()
            records.append(engine.last_step)
        for rid, prompt, _, seed in reqs[3:5]:
            engine.submit(prompt, 6, rid=rid, seed=seed, klass="gold")
        return engine, records + drive(engine), firsts, None
    engine = toys.engine_of(model, variables, "greedy", "chunked")
    firsts = counting_first_tokens(engine)
    submit_all(engine, vocab)
    plan = {
        "plain": [],
        "prefill_fault": [{"point": "serve.prefill_chunk", "action": "reset", "after": 2, "times": 1}],
        "step_fault": [{"point": "serve.step", "action": "reset", "after": 3, "times": 1}],
        "admit_fault": [{"point": "serve.admit", "action": "reset", "after": 1, "times": 1}],
    }[scenario]
    if plan:
        faults.install_plan(plan, export_env=False)
    return engine, drive(engine), firsts, want_of("dense")


SCENARIOS = ["plain", "pool_pressure", "class_preemption", "prefill_fault", "step_fault",
             "admit_fault"]


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_counts_add_up_over_a_run(built, no_fault_plan, scenario):
    engine, records, firsts, want = interrupted(built, scenario)
    m, done = engine.metrics, engine.completions
    assert [r.call for r in records] == list(range(1, len(records) + 1))
    assert sum(r.admitted for r in records) == m.admitted
    assert sum(r.retired for r in records) == m.completed == len(done)
    assert sum(len(r.chunks) for r in records) == m.prefill_chunks
    assert sum(1 for r in records if r.decode_keys) == m.decode_steps
    assert sum(r.prompt_tokens_admitted for r in records) >= sum(
        c.prompt_len for c in done.values())
    booked = sum(r.tokens_booked for r in records)
    given = sum(len(c.tokens) for c in done.values())
    evicted = sum(r.preempted for r in records)
    assert booked == given if not evicted else booked >= given
    # every result dispatched is read back once: a decode step's tokens, a
    # finished prefill's first token
    assert sum(r.resolved for r in records) == m.decode_steps + len(firsts)
    assert records[-1].flush == "idle" and not records[-1].chunks
    by_cause = {
        "pool_pressure": m.preempted,
        "class_preemption": m.class_preempted,
        "prefill_fault": m.requeued,
        "step_fault": m.requeued,
    }
    assert evicted == by_cause.get(scenario, 0)
    if scenario in by_cause:
        assert evicted > 0
    if scenario == "admit_fault":
        assert m.requeued == 1  # sent back at the door: it never held a slot
    if scenario == "step_fault":
        (hit,) = [r for r in records if r.flush == "requeue"]
        assert hit.preempted == m.requeued and not hit.decode_keys
    for r in records:
        assert set(r.host_s) == set(PHASES)
        assert all(v >= 0.0 for v in r.host_s.values())
        assert sum(r.host_s.values()) <= r.step_s + 1e-9
    if want is not None:
        assert tokens_of(done) == want


def test_the_queue_depth_is_the_gauge_s_and_admissions_count_their_tokens(built):
    model, variables, vocab = toy(built, "dense")
    engine = toys.engine_of(model, variables, "greedy", "chunked")
    submit_all(engine, vocab)
    engine.step()
    first = engine.last_step
    reqs = toys.requests(vocab)
    assert first.admitted == 3 and first.queue_depth == len(reqs) - 3
    assert first.queue_depth == engine.metrics.queue_depth
    assert first.prompt_tokens_admitted == sum(len(p) for _, p, _, _ in reqs[:3])
    assert first.prefix_tokens_attached == 0 and first.resolved == 0
    assert engine._rec.call == 2 and engine._rec.t0 is None  # the next call's, not begun


# --- every cause of a flush --------------------------------------------------------

@pytest.mark.parametrize("cause", ["caller", "snapshot", "drain", "requeue", "handoff", "idle"])
def test_every_flush_says_its_cause_in_a_record(built, cause):
    """Inside a call the flush is the call's own (`idle`; `requeue` after a
    step fault is in the test above); a seam between calls fills a record
    of its own, with the next call's index."""
    model, variables, vocab = toy(built, "dense")
    role = "prefill" if cause == "handoff" else "both"
    engine = toys.engine_of(model, variables, "greedy", "chunked", role=role)
    for rid, prompt, _, seed in toys.requests(vocab)[:3]:
        engine.submit(prompt, 2 if cause == "idle" else 20, rid=rid, seed=seed)
    if cause == "idle":
        records = drive(engine)
        assert [r.flush for r in records][-1] == "idle"
        assert all(r.flush is None for r in records[:-1])
        assert records[-1].resolved >= 1 and not records[-1].decode_keys
        return
    # a prefill pool's only results are first tokens: one is out after a call
    for _ in range(1 if cause == "handoff" else 4):
        engine.step()
    before, outstanding = engine.last_step, len(engine._inflight)
    assert outstanding >= 1 and before.flush is None
    {"caller": engine.flush, "snapshot": engine.snapshot_state, "drain": engine.drain,
     "requeue": engine.requeue_inflight, "handoff": engine.pop_handoffs}[cause]()
    rec = engine.last_step
    assert rec is not before and rec.call == before.call + 1
    assert rec.flush == cause and rec.resolved == outstanding
    assert rec.tokens_booked >= 1 and not rec.chunks and not rec.decode_keys
    assert rec.host_s["wait"] + rec.host_s["book"] <= rec.step_s
    assert rec.host_s["admit"] == rec.host_s["decode_tick"] == 0.0
    assert engine.metrics.pipeline_flushes[cause] == 1
    # the window's host block counts it as a call
    assert engine.metrics.snapshot()["window"]["host"]["calls"] == rec.call
    if cause in ("drain", "requeue"):
        # what the seam sent back to the queue is booked to the call that follows
        assert engine._rec.preempted == engine.metrics.requeued >= 1
        engine.step()
        assert engine.last_step.preempted == engine.metrics.requeued


# --- stamps where the request changes hands ------------------------------------------

def test_a_request_is_stamped_at_admission_and_once_a_token(built):
    model, variables, vocab = toy(built, "dense")
    clock = Ticks()
    engine = toys.engine_of(model, variables, "greedy", "chunked", clock=clock)
    submit_all(engine, vocab)
    done = drive(engine) and engine.completions
    assert tokens_of(done) == want_of("dense")
    for c in done.values():
        assert len(c.token_times) == len(c.tokens)
        assert all(b > a for a, b in zip(c.token_times, c.token_times[1:]))
        arrival = c.token_times[0] - c.ttft_s
        assert c.queue_s > 0 and arrival + c.queue_s < c.token_times[0]
        assert c.token_times[-1] - arrival == pytest.approx(c.e2e_s)
        if len(c.tokens) > 1:
            assert (c.token_times[-1] - c.token_times[0]) / (len(c.tokens) - 1) == (
                pytest.approx(c.tpot_s))
    # the three that found a slot at once waited less than those behind them
    waits = sorted(c.queue_s for c in done.values())
    assert waits[2] < waits[3]
    view = stamps_view(engine)
    assert view["queue_ms"]["n"] == len(done)
    assert view["itl_ms"]["n"] == sum(len(c.tokens) - 1 for c in done.values())
    assert view["queue_ms"]["p50"] == pytest.approx(
        1e3 * float(np.percentile([c.queue_s for c in done.values()], 50)), abs=1e-3)
    gaps = np.concatenate([np.diff(c.token_times) for c in done.values()])
    assert view["itl_ms"]["p90"] == pytest.approx(1e3 * float(np.percentile(gaps, 90)), abs=1e-3)


def test_a_requeued_request_s_stamps_are_its_replay_s_own(built):
    model, variables, vocab = toy(built, "dense")
    clock = Ticks()
    engine = toys.engine_of(model, variables, "greedy", "chunked", clock=clock)
    submit_all(engine, vocab)
    for _ in range(4):
        engine.step()
    slot = min(engine._decoding)
    evicted = engine._slot_req[slot]
    assert len(evicted.token_times) >= 1
    first_admission = evicted.admit_time
    engine._evict(slot, requeue_counter=False)
    at = clock.t
    records = drive(engine)
    done = engine.completions
    c = done[evicted.rid]
    assert c.requeues == 1 and c.tokens == want_of("dense")[evicted.rid]
    assert len(c.token_times) == len(c.tokens) and min(c.token_times) > at
    assert evicted.admit_time > at > first_admission
    assert c.queue_s == pytest.approx(evicted.admit_time - evicted.arrival_time)
    # it happened between two calls: the call that followed says so
    assert [r.preempted for r in records] == [1] + [0] * (len(records) - 1)
    assert stamps_view(engine)["itl_ms"]["n"] == sum(
        len(x.tokens) - 1 for x in done.values())


def test_a_migrated_request_lands_with_the_one_stamp_it_brings(built):
    """A decode pool admits a request that holds its first token: one stamp
    (the prefill pool's), an admission of its own, and a token a step after."""
    model, variables, vocab = toy(built, "dense")
    (_, prompt, _, seed) = toys.requests(vocab)[1]
    clock = Ticks()
    pre = toys.engine_of(model, variables, "greedy", "chunked", role="prefill", clock=clock)
    dec = toys.engine_of(model, variables, "greedy", "chunked", role="decode", clock=clock)
    pre.submit(prompt, 6, rid="moves", seed=seed)
    while pre.step():
        pass
    (h,) = pre.pop_handoffs()
    payload = pre.cache.export_blocks(pre.cache.slot_blocks(h.slot))
    assert dec.attach_migrated(h.req, h.length, h.first, payload) is not None
    pre.release_handoff(h)
    assert h.req.token_times == [h.req.first_token_time]
    records = drive(dec)
    assert [r.admitted for r in records][0] == 1  # landed before the first call
    done = dec.completions
    c = done["moves"]
    assert c.tokens == want_of("dense")["r1"][:6]
    assert len(c.token_times) == 6 and c.token_times[0] == h.req.first_token_time


# --- nothing is different with a trace on -----------------------------------------------

def traced(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)


def host_events(tmp_path):
    """[(name, start_ns, end_ns, args)] of the `serve:` events, in order."""
    from bench_matrix.readers import engine_steps
    from bench_matrix.reduce import xplane

    return engine_steps.parse_file(xplane.find(str(tmp_path)))


@pytest.fixture(scope="module")
def traced_run(built, tmp_path_factory):
    """The sparse toy's workload twice on a clock that counts its reads:
    untraced, then under a profiler trace."""
    import jax

    model, variables, vocab = toy(built, "sparse_window")
    runs = []
    for trace_dir in (None, tmp_path_factory.mktemp("trace")):
        engine = toys.engine_of(model, variables, "greedy", "chunked", clock=Ticks())
        submit_all(engine, vocab)
        if trace_dir:
            traced(trace_dir)
        try:
            records = drive(engine)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        runs.append({"engine": engine, "records": records, "dir": trace_dir})
    return runs


def test_tokens_and_records_are_identical_traced_and_untraced(traced_run):
    plain, under_trace = traced_run
    assert tokens_of(plain["engine"].completions) == want_of("sparse_window")
    assert tokens_of(under_trace["engine"].completions) == want_of("sparse_window")
    # every field, the phase seconds too: the clock counts reads, and a call
    # reads it as often whether or not anything observes it
    assert plain["records"] == under_trace["records"]
    assert plain["engine"].metrics.snapshot()["window"]["host"] == (
        under_trace["engine"].metrics.snapshot()["window"]["host"])
    for a, b in zip(plain["engine"].completions.values(),
                    under_trace["engine"].completions.values()):
        assert a == b


def test_a_record_keeps_no_device_value(traced_run):
    def plain_values(x):
        if isinstance(x, (tuple, list)):
            return all(plain_values(v) for v in x)
        if isinstance(x, dict):
            return all(plain_values(v) for v in x.values())
        return x is None or type(x) in (int, float, str)

    records = traced_run[0]["records"]
    assert any(r.moe for r in records)
    for r in records:
        assert isinstance(r, StepRecord)
        assert plain_values(dataclasses.astuple(r)), r


def test_the_trace_holds_every_span_with_the_record_s_arguments(traced_run):
    run = traced_run[1]
    records, found = run["records"], host_events(run["dir"])
    by_name = {}
    for ev in found:
        by_name.setdefault(ev[0], []).append(ev)
    steps = by_name["serve:step"]
    assert len(steps) == len(records)
    # one `serve:step_done` a call, inside the call's span, with its counts
    assert [a for _, _, _, a in by_name["serve:step_done"]] == [
        dict(call=r.call, queue=r.queue_depth, admitted=r.admitted,
             prompt=r.prompt_tokens_admitted, attached=r.prefix_tokens_attached,
             chunks=len(r.chunks),
             chunk_tokens=sum(c[2] for c in r.chunks), rows=r.decode_rows,
             keys=sum(r.decode_keys), resolved=r.resolved, booked=r.tokens_booked,
             retired=r.retired, preempted=r.preempted) for r in records]
    for (_, s, e, _), (_, ds, de, _) in zip(steps, by_name["serve:step_done"]):
        assert s <= ds and de <= e

    def inside(name, outer):
        spans = by_name[outer]
        return all(any(s <= ev[1] and ev[2] <= e for _, s, e, _ in spans)
                   for ev in by_name[name])

    for phase in PHASES:
        assert by_name["serve:" + phase] and inside("serve:" + phase, "serve:step"), phase
    assert {a["first"] for _, _, _, a in by_name["serve:wait"]} == {0, 1}
    assert len(by_name["serve:wait"]) == len(by_name["serve:book"]) == sum(
        r.resolved for r in records)
    assert len(by_name["serve:gauges"]) == len(records)
    # an admission: the prompt, what the prefix cache matched, the wait
    admitted = [a for _, _, _, a in by_name["serve:admitted"]]
    assert len(admitted) == sum(r.admitted for r in records) == 6
    assert sum(a["prompt"] for a in admitted) == sum(r.prompt_tokens_admitted for r in records)
    assert all(a["attached"] == 0 and a["queue_us"] > 0 for a in admitted)
    assert inside("serve:admitted", "serve:admit")
    # the three older annotations keep their names and arguments, and nest
    assert [(a["slot"], a["start"], a["tokens"], a["bucket"])
            for _, _, _, a in by_name["serve:prefill_chunk"]] == [
                c for r in records for c in r.chunks]
    assert [(a["rows"], a["keys"]) for _, _, _, a in by_name["serve:decode_step"]] == [
        (r.decode_rows, sum(r.decode_keys)) for r in records if r.decode_keys]
    assert inside("serve:prefill_chunk", "serve:prefill_tick")
    assert inside("serve:decode_step", "serve:decode_tick")
    moe = [r.moe for r in records if r.moe]
    assert len(moe) == run["engine"].metrics.moe_steps == len(by_name["serve:moe_step"])
    for (_, _, _, a), m in zip(by_name["serve:moe_step"], moe):
        assert (a["rows"], a["assignments"], a["routed"]) == (
            m["rows"], m["assignments"], m["routed"])
        assert [a[f"hit{i}"] for i in range(len(m["experts_hit"]))] == m["experts_hit"]
    assert inside("serve:moe_step", "serve:book")
    # nothing is written that no reader knows: the spans are these
    assert set(by_name) == {"serve:step", "serve:step_done", "serve:admitted",
                            "serve:prefill_chunk", "serve:decode_step", "serve:moe_step",
                            *("serve:" + p for p in PHASES)}
    # a span's length on the profiler's clock is the record's on the engine's
    # only with a real clock; here the spans are just well formed
    assert all(e >= s for _, s, e, _ in found)


def test_a_prefix_hit_is_in_the_admission_s_record_and_stamp(built):
    """Two prompts that open alike: the second admission attaches the
    blocks the first indexed, and says how many tokens."""
    model, variables, vocab = toy(built, "dense")
    engine = toys.engine_of(model, variables, "greedy", "chunked", prefix_cache=True)
    gen = np.random.default_rng(5)
    head = gen.integers(0, vocab, (16,)).astype(np.int32)
    a = np.concatenate([head, gen.integers(0, vocab, (5,)).astype(np.int32)])
    b = np.concatenate([head, gen.integers(0, vocab, (7,)).astype(np.int32)])
    engine.submit(a, 3, rid="a")
    first = drive(engine)
    engine.submit(b, 3, rid="b")
    second = drive(engine)
    assert sum(r.prefix_tokens_attached for r in first) == 0
    assert sum(r.prefix_tokens_attached for r in second) == 16
    assert sum(r.prefix_tokens_attached for r in first + second) == (
        engine.prefix.stats()["prefix_tokens_reused"])
    assert [c[1] for r in second for c in r.chunks][0] == 16  # prefill starts behind it


# --- the keys a decode step reads once for several rows -------------------------------------

def wide(built):
    """A float32 model at a head size the decode kernel takes (Dh = 128)."""
    if "wide" not in built:
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import TransformerConfig, TransformerLM

        model = TransformerLM(TransformerConfig(
            vocab_size=64, d_model=512, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
            max_seq_len=320, use_flash=False))
        built["wide"] = model, model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return built["wide"]


@pytest.mark.parametrize("prefix_cache", [True, False], ids=["prefix_cache", "no_prefix_cache"])
def test_the_record_counts_the_keys_the_kernel_reads_once_for_several_rows(
        built, tmp_path, prefix_cache):
    """Four requests behind one 272-token head (a whole 256-key compute
    block and a page) decode together: `decode_shared_keys` of every call is
    what the kernel's own work list shares for the tables the step was
    handed (one rule, `ops.paged_attention.shared_runs`), it rides on
    `serve:decode_step`, and `/serve` gives its share of the keys attended.
    Without `prefix_cache` no two rows hold one block: the device's shared
    list is empty and the count 0."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.ops import paged_attention
    from pytorch_distributed_example_tpu.serve import ServeEngine

    model, variables = wide(built)
    engine = ServeEngine(model, variables, slots=4, min_bucket=16, block_size=16,
                         prefill_chunk_tokens=64, prefix_cache=prefix_cache)
    assert engine._decode_kernel
    gen = np.random.default_rng(9)
    head = gen.integers(0, 64, (272,)).astype(np.int32)
    prompts = [np.concatenate([head, gen.integers(0, 64, (n,)).astype(np.int32)])
               for n in (4, 11, 7, 18)]
    program, handed = engine._step, []

    def keeping(params, tree, lengths, tokens, rngs, bt):
        handed.append((np.array(bt), np.array(lengths)))
        return program(params, tree, lengths, tokens, rngs, bt)

    engine._step = keeping
    records = []
    traced(tmp_path)
    try:
        engine.submit(prompts[0], 12)
        while not engine.metrics.decode_steps:  # its head is indexed by then
            engine.step()
            records.append(engine.last_step)
        for p in prompts[1:]:
            engine.submit(p, 6)
        records += drive(engine)
    finally:
        jax.profiler.stop_trace()
    records = [r for r in records if r.decode_keys]
    assert len(handed) == len(records) == engine.metrics.decode_steps
    nblk, P = engine.cache.invalid_block, 16
    listed = []  # (keys the device's list shares, shared items) a step
    for tables, lengths in handed:
        scalars = paged_attention._work_list(
            jnp.asarray(tables), jnp.asarray(lengths), nblk, 16, P, share=True)
        skip, n_shared = np.asarray(scalars[6]), int(np.asarray(scalars[9])[0])
        listed.append((int(skip.sum()) * P * 16, n_shared))
    assert [r.decode_shared_keys for r in records] == [keys for keys, _ in listed]
    spans = [a for name, _, _, a in host_events(tmp_path) if name == "serve:decode_step"]
    assert [(a["rows"], a["shared"]) for a in spans] == [
        (r.decode_rows, r.decode_shared_keys) for r in records]
    decode = engine.metrics.snapshot()["decode"]
    if prefix_cache:
        assert max(r.decode_shared_keys for r in records) == 4 * 256
        assert all(n == (1 if keys else 0) for keys, n in listed)
        assert 0.5 < decode["shared_key_share"] < 256 / 272
    else:
        assert all(r.decode_shared_keys == 0 for r in records)
        assert all(n == 0 for _, n in listed)
        assert decode["shared_key_share"] == 0.0
    assert engine.metrics.decode_keys == sum(sum(r.decode_keys) for r in records)
    assert decode["shared_key_share"] == round(
        sum(r.decode_shared_keys for r in records) / engine.metrics.decode_keys, 4)


# --- what an operator reads ---------------------------------------------------------------

def test_the_serve_page_shows_the_host_s_share_of_a_call(built):
    from pytorch_distributed_example_tpu.utils.debug_http import DebugServer

    model, variables, vocab = toy(built, "dense")
    engine = toys.engine_of(model, variables, "greedy", "chunked", clock=Ticks())
    submit_all(engine, vocab)
    records = drive(engine)
    srv = DebugServer()
    try:
        srv.register_serve_metrics("engine", engine.metrics)
        with urllib.request.urlopen(srv.url + "/serve") as r:
            host = json.loads(r.read())["engine"]["window"]["host"]
    finally:
        srv.shutdown()
    n = len(records)
    total = sum(r.step_s for r in records)
    wait = sum(r.host_s["wait"] for r in records)
    assert host["calls"] == n and set(host["phase_ms"]) == set(PHASES)
    assert host["step_ms"] == pytest.approx(1e3 * total / n, abs=1e-3)
    assert host["work_ms"] == pytest.approx(1e3 * (total - wait) / n, abs=1e-3)
    assert host["wait_share"] == pytest.approx(wait / total, abs=1e-3)
    for phase in PHASES:
        assert host["phase_ms"][phase] == pytest.approx(
            1e3 * sum(r.host_s[phase] for r in records) / n, abs=1e-3)
    longest = max(records, key=lambda r: r.step_s)
    assert host["longest"]["call"] == longest.call
    assert host["longest"]["step_ms"] == pytest.approx(1e3 * longest.step_s, abs=1e-3)
    assert host["longest"]["phase_ms"]["prefill_tick"] == pytest.approx(
        1e3 * longest.host_s["prefill_tick"], abs=1e-3)
    assert host["longest"]["chunks"] == len(longest.chunks)
    # every count of the record has this reader: none is filled for nobody
    for key in ("queue_depth", "admitted", "prompt_tokens_admitted", "prefix_tokens_attached",
                "decode_rows", "resolved", "retired", "preempted", "flush", "moe"):
        assert host["longest"][key] == getattr(longest, key), key
    # a window that holds no call says so and divides by nothing
    assert stamps_view(engine, window_s=1e-9)["host"] == {"calls": 0}
    # the controller's poll neither holds the three blocks nor pays for them
    assert not {"host", "queue_ms", "itl_ms"} & set(engine.metrics.window_view())
