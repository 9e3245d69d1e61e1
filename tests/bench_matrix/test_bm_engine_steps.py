"""The `engine_steps` reader and the cell that lists its metrics: the phase
table by hand on a slice of a live CPU trace of a tiny engine with the
prefix cache on, `prefix_hit_share` against the index's own count, keys left
out where there is nothing to read, idle gaps named by the innermost span,
the traffic mix, and `serve_sessions_prefix` end to end at a tiny preset."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench_matrix import spec, traffic_gen
from bench_matrix.readers import ReadEnv, engine_steps
from bench_matrix.reduce import xplane

from _tiny import FAKE_PEAKS, TINY_MODEL, context, tiny_cell

CELL = "serve_sessions_prefix"
SEVEN = ("serve_host_work_ms", "serve_host_wait_pct", "serve_admit_ms", "serve_dispatch_ms",
         "serve_book_ms", "serve_queue_wait_ms", "prefix_hit_share")
ARGS = {m: spec.load("layer_metrics", m)["args"] for m in SEVEN}
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
DEV = "/device:TPU:0"


def _env(name, trace=None):
    said = []
    return ReadEnv(cell={"name": name}, samples={}, trace=trace or xplane.Trace(),
                   peaks=FAKE_PEAKS, chips=1, memory_peak_bytes=0, say=said.append), said


def _trace_into(directory):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(directory), profiler_options=opts)


@pytest.fixture(scope="module")
def live(tmp_path_factory):
    """A tiny engine with the prefix cache on, traced on the CPU while it
    serves eight prompts that open with one of two shared heads: the
    events the reader finds, the engine's records, the index's count."""
    import jax

    from bench_matrix import modelglue
    from pytorch_distributed_example_tpu.serve import ServeEngine

    out = tmp_path_factory.mktemp("engine_steps")
    cfg = dict(spec.load("configs", "mistral-7b-v0.3-d16"), **TINY_MODEL)
    model = modelglue.build_model(cfg, 128, remat=False)
    engine = ServeEngine(model, modelglue.make_variables(model, cfg, 3), slots=3,
                         block_size=8, pool_blocks=64, prefill_chunk_tokens=32,
                         min_bucket=16, prefix_cache=True)
    gen = np.random.default_rng(7)
    heads = gen.integers(0, 256, (2, 32), dtype=np.int32)
    engine.submit(np.concatenate([heads[0], [1, 2, 3]]).astype(np.int32), 2, rid="warm0")
    engine.submit(np.concatenate([heads[1], [4, 5]]).astype(np.int32), 2, rid="warm1")
    engine.run()
    reused_before = engine.prefix.stats()["prefix_tokens_reused"]
    _trace_into(out / "trace" / "tiny")
    records = []
    try:
        for i in range(8):
            tail = gen.integers(0, 256, (int(gen.integers(3, 20)),), dtype=np.int32)
            engine.submit(np.concatenate([heads[i % 2], tail]), 4, rid=f"r{i}")
        while engine.step():
            records.append(engine.last_step)
        records.append(engine.last_step)
    finally:
        jax.profiler.stop_trace()
    return {"out": out, "records": records,
            "reused": engine.prefix.stats()["prefix_tokens_reused"] - reused_before}


@pytest.fixture()
def found(live, monkeypatch):
    from bench_matrix import run

    monkeypatch.setattr(run, "OUT_DIR", str(live["out"]))
    monkeypatch.setattr(engine_steps, "_PARSED", {})
    env, said = _env("tiny")
    events = engine_steps.events(env)
    assert events and len(said) == 2  # the table, said with a trace's first read
    assert engine_steps.events(env) is events and len(said) == 2
    return events


def by_hand(events, lo, hi):
    """{name: summed ns} of the events that start in [lo, hi], the
    `serve:step` spans themselves under their own name."""
    total = {}
    for name, s, e, _ in events:
        if lo <= s <= hi:
            total[name] = total.get(name, 0) + e - s
    return total


def test_the_phase_table_of_a_two_call_slice_is_the_sum_by_hand(found, live):
    steps = [ev for ev in found if ev[0] == "serve:step"]
    assert len(steps) == len(live["records"]) >= 6
    (_, lo, _, _), (_, _, hi, _) = steps[2], steps[3]
    kept = engine_steps.calls(found, (lo, hi))
    assert [(c["start"], c["end"]) for c in kept] == [(s, e) for _, s, e, _ in steps[2:4]]
    ns = by_hand(found, lo, hi)
    step, wait = ns["serve:step"], ns["serve:wait"]
    want = {
        "serve_host_work_ms": (step - wait) / 1e6 / 2,
        "serve_host_wait_pct": 100.0 * wait / step,
        "serve_admit_ms": (ns["serve:admit"] + ns["serve:gauges"]) / 1e6 / 2,
        "serve_dispatch_ms": (ns["serve:prefill_tick"] + ns["serve:decode_tick"]) / 1e6 / 2,
        "serve_book_ms": ns["serve:book"] / 1e6 / 2,
    }
    got = {m: engine_steps.stat(ARGS[m], kept) for m in want}
    assert got == pytest.approx(want, rel=1e-12)
    # what the issue asks of the numbers: the share and the work make the call
    mean_step_ms = step / 1e6 / 2
    assert got["serve_host_wait_pct"] + 100 * got["serve_host_work_ms"] / mean_step_ms == (
        pytest.approx(100.0))
    assert got["serve_admit_ms"] + got["serve_dispatch_ms"] + got["serve_book_ms"] <= (
        got["serve_host_work_ms"])


def test_a_call_cut_by_the_slice_s_edge_is_dropped(found):
    steps = [ev for ev in found if ev[0] == "serve:step"]
    whole = engine_steps.calls(found)
    assert len(whole) == len(steps)
    (_, lo, _, _), (_, _, hi, _) = steps[1], steps[4]
    assert len(engine_steps.calls(found, (lo, hi))) == 4
    assert len(engine_steps.calls(found, (lo + 1, hi))) == 3  # the first began before it
    assert len(engine_steps.calls(found, (lo, hi - 1))) == 3  # the last ends after it
    cut = engine_steps.calls(found, (lo + 1, hi - 1))
    assert [(c["start"], c["end"]) for c in cut] == [(s, e) for _, s, e, _ in steps[2:4]]
    # what a dropped call holds is in no kept call
    held = {id(ev) for c in cut for ev in c["events"]}
    assert all(id(ev) not in held for ev in found
               if ev[1] < steps[2][1] or ev[1] > steps[3][2])
    assert engine_steps.calls(found, (hi, hi + 10)) == []
    assert engine_steps.stat(ARGS["serve_host_work_ms"], []) is None


def test_the_hit_share_is_the_index_s_own_count(found, live):
    kept = engine_steps.calls(found)
    records = live["records"]
    prompts = sum(r.prompt_tokens_admitted for r in records)
    assert live["reused"] == sum(r.prefix_tokens_attached for r in records) == 8 * 32
    assert engine_steps.stat(ARGS["prefix_hit_share"], kept) == pytest.approx(
        100.0 * live["reused"] / prompts)
    waits = [a["queue_us"] for name, _, _, a in found if name == "serve:admitted"]
    assert len(waits) == 8
    assert engine_steps.stat(ARGS["serve_queue_wait_ms"], kept) == pytest.approx(
        sum(waits) / 8 / 1e3)
    # the spans say what the records say, call for call
    done = [a for name, _, _, a in found if name == "serve:step_done"]
    assert [(a["call"], a["admitted"], a["chunks"], a["rows"]) for a in done] == [
        (r.call, r.admitted, len(r.chunks), r.decode_rows) for r in records]


def test_the_shared_key_share_is_the_records_own_and_0_where_nothing_is_shared(found, live):
    """`shared_key_share`: of the keys the slice's decode steps attend, those
    the kernel reads once for several rows, from the `serve:decode_step`
    annotations. Where the kernel shares nothing (heads shorter than its 256-key
    blocks here; a latent pool on the chip) it reads 0.0: a count, not a share
    of a peak, so 0 is a reading and the key stays."""
    args = spec.load("layer_metrics", "shared_key_share")
    assert (args["reader"], args["source"], args["layer"], args["moves"]) == (
        "engine_steps", "program_counter", "serve plane", "serve_tokens_per_s")
    records = [r for r in live["records"] if r.decode_keys]
    keys = sum(sum(r.decode_keys) for r in records)
    shared = sum(r.decode_shared_keys for r in records)
    steps = [a for name, _, _, a in found if name == "serve:decode_step"]
    assert [(a["rows"], a["keys"], a["shared"]) for a in steps] == [
        (r.decode_rows, sum(r.decode_keys), r.decode_shared_keys) for r in records]
    got = engine_steps.stat(args["args"], engine_steps.calls(found))
    assert got == pytest.approx(100.0 * shared / keys) and got == 0.0
    # a slice whose steps share: 3 of 4 keys behind one head
    call = {"start": 0, "end": 9, "ns": {}, "events": [
        ("serve:decode_step", 1, 2, {"rows": 8, "keys": 4000, "shared": 3000}),
        ("serve:decode_step", 3, 4, {"rows": 8, "keys": 4008, "shared": 3006})]}
    assert engine_steps.stat(args["args"], [call]) == pytest.approx(100.0 * 6006 / 8008)


def test_the_loop_counts_a_step_s_distinct_keys_from_the_pool_s_tables():
    """Three requests decode behind one 32-token head that the prefix index
    gave them (four blocks of 8): the step's rows attend the head's keys three
    times and the pool holds them once, whatever the kernel does about it
    (`decode_shared_keys` is 0 here: the kernel shares whole 256-key blocks
    only). The runner counts from the tables and lengths the step was handed."""
    import jax

    from bench_matrix import modelglue
    from bench_matrix.runners.serve import _Loop
    from pytorch_distributed_example_tpu.serve import ServeEngine

    cfg = dict(spec.load("configs", "mistral-7b-v0.3-d16"), **TINY_MODEL)
    model = modelglue.build_model(cfg, 128, remat=False)
    engine = ServeEngine(model, modelglue.make_variables(model, cfg, 3), slots=4,
                         block_size=8, pool_blocks=64, prefill_chunk_tokens=32,
                         min_bucket=16, prefix_cache=True)
    gen = np.random.default_rng(11)
    head = gen.integers(0, 256, (32,), dtype=np.int32)
    engine.submit(np.concatenate([head, [1, 2, 3]]).astype(np.int32), 2, rid="first")
    engine.run()  # the head is indexed
    ctx = context(1.0, jax.devices()[:1])
    ctx.compiles.close()
    loop = _Loop(engine, tiny_cell(CELL)["traffic"], 256, ctx)
    loop._count()
    for i, n in enumerate((5, 9, 14)):
        engine.submit(np.concatenate([head, gen.integers(0, 256, (n,), dtype=np.int32)]), 6,
                      rid=f"r{i}")
    seen = []
    while engine.step():
        rec = engine.last_step
        if rec.decode_keys:
            seen.append((rec.decode_keys, loop._distinct_keys(rec.decode_keys)))
        loop._count()
    assert max(len(k) for k, _ in seen) == 3 and engine.last_step.decode_shared_keys == 0
    for keys, distinct in seen:
        assert distinct == sum(keys) - 32 * (len(keys) - 1), (keys, distinct)
    # lengths that are not the record's: not counted, never guessed
    assert loop._distinct_keys((1, 2, 3)) is None


def test_the_table_names_every_phase_and_the_longest_call(found):
    (phases, longest) = engine_steps.table(found, xplane.Trace())[:2]
    assert phases.startswith("engine steps: ")
    for phase in ("admit", "gauges", "prefill_tick", "decode_tick", "wait", "book", "no_phase"):
        assert f"{phase} " in phases, phase
    assert longest.startswith("longest call ") and "'call': " in longest
    kept = engine_steps.calls(found)
    whole = sum(c["end"] - c["start"] for c in kept)
    assert f"{whole / 1e6 / len(kept):.3f} ms a call" in phases


def test_every_key_is_left_out_where_there_is_nothing_to_read(live, monkeypatch, tmp_path):
    import jax
    import jax.numpy as jnp

    from bench_matrix import run

    # no traced slice
    env, _ = _env("tiny")
    env.trace = None
    assert all(engine_steps.read(ARGS[m], env) is None for m in SEVEN)
    # a trace directory that is not there
    monkeypatch.setattr(run, "OUT_DIR", str(live["out"]))
    assert engine_steps.read(ARGS["prefix_hit_share"], _env("no_such_cell")[0]) is None
    # a program that writes no such span (the parent's): a trace without one
    _trace_into(tmp_path / "trace" / "other")
    try:
        jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(run, "OUT_DIR", str(tmp_path))
    env, said = _env("other")
    assert all(engine_steps.read(ARGS[m], env) is None for m in SEVEN)
    assert said == []
    # spans, but no admission in the slice: the two that read admissions only
    steps = [("serve:step", 0, 100, {}), ("serve:wait", 10, 60, {"first": 0})]
    kept = engine_steps.calls(steps)
    assert engine_steps.stat(ARGS["prefix_hit_share"], kept) is None
    assert engine_steps.stat(ARGS["serve_queue_wait_ms"], kept) is None
    assert engine_steps.stat(ARGS["serve_host_wait_pct"], kept) == 50.0
    with pytest.raises(ValueError, match="unknown statistic"):
        engine_steps.stat({"stat": "median"}, kept)


# --- idle gaps by the innermost span -----------------------------------------------

SPANS = [
    ("serve:step", 0, 1000, {}),
    ("serve:admit", 10, 100, {}),
    ("serve:prefill_tick", 100, 400, {}),
    ("serve:prefill_chunk", 150, 380, {"slot": 0}),
    ("serve:wait", 500, 900, {"first": 0}),
    ("serve:step", 1100, 2000, {}),
]
GAPS = {
    "inside_the_chunk_s_dispatch": ((200, 300), "serve:prefill_chunk"),
    "inside_the_tick_beside_the_chunk": ((100, 140), "serve:prefill_tick"),
    "inside_the_readback": ((600, 700), "serve:wait"),
    "in_the_call_between_two_phases": ((410, 490), "serve:step"),
    "mostly_in_the_readback": ((450, 800), "serve:wait"),
    "between_two_calls": ((1010, 1090), "no span"),
    "across_the_seam_mostly_outside": ((950, 1090), "serve:step"),
}


@pytest.mark.parametrize("case", sorted(GAPS))
def test_a_gap_is_named_after_the_innermost_span_that_covers_most_of_it(case):
    (lo, hi), name = GAPS[case]
    assert engine_steps.innermost(SPANS, lo, hi) == name


def test_the_longest_device_gaps_come_with_their_spans():
    # in microseconds: device operations everywhere but in three holes of 3000,
    # 1000 and 1200 (behind the chunk's dispatch, and two inside the readback)
    # and one of 500 inside the second call
    ops = [("op", 0, 2000), ("op", 5000, 6000), ("op", 7000, 7800), ("op", 9000, 15000),
           ("op", 15500, 19000)]
    us = 1000
    trace = xplane.Trace(devices={DEV: [(n, us * s, us * e) for n, s, e in ops]})
    spans = [(n, 10 * us * s, 10 * us * e, a) for n, s, e, a in SPANS]
    gaps = engine_steps.idle_gaps(spans, trace, n=2)
    assert gaps == [["serve:prefill_chunk", 3000e-6], ["serve:wait", 1200e-6]]
    # a gap shorter than the two clocks agree is given its length and no name
    assert engine_steps.idle_gaps(spans, trace, n=5)[2:] == [
        ["serve:wait", 1000e-6], [engine_steps.UNNAMED, 500e-6]]
    assert engine_steps.idle_gaps(spans, xplane.Trace()) == []
    # the slice is the device window: the second call ends after it and is dropped
    assert len(engine_steps.kept(spans, trace)) == 1
    lines = engine_steps.table(spans, trace)
    assert lines[-1].startswith("longest device idle gaps by innermost `serve:` span")
    assert engine_steps.UNNAMED + " 500.0" in lines[-1]


# --- the cell and its files --------------------------------------------------------

def test_the_seven_metrics_are_the_serve_plane_s_and_the_cell_lists_them():
    cell = spec.load_cell(CELL)
    assert cell["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SEVEN:
        m = cell["per_layer"][name]
        assert (m["layer"], m["moves"], m["reader"]) == (
            "serve plane", "serve_tokens_per_s", "engine_steps")
        assert m["source"] == ("program_counter" if name == "prefix_hit_share"
                               else "program_span")
        assert CELL in listed[name]["workloads"]
    assert set(SEVEN) <= set(listed)
    assert CELL in [w["name"] for w in BENCH["workloads"]]
    # every accepted metric the cell reports lists it, and moves what it reports
    for name, m in cell["per_layer"].items():
        assert CELL in listed[name]["workloads"] and m["moves"] in cell["end_to_end"]
    # since PR 40 the decode roofline's bytes are the step's DISTINCT keys, so the
    # cell whose rows share four heads lists it, beside the share of a step's keys
    # the kernel reads once for several rows and the window's share of the peak
    assert {"paged_decode_roofline", "shared_key_share", "serve_mfu_pct"} <= set(cell["per_layer"])
    # the check attaches (`runners/serve_prefix.py`), at serve_decode_c32's limits
    mine, accepted = cell["correctness"], spec.load("workloads", "serve_decode_c32")["correctness"]
    assert cell["runner"] == "serve_prefix"
    for key in ("decode_positions", "last_positions", "max_rel", "rms_rel", "chosen_gap"):
        assert mine[key] == accepted[key], key
    eng = cell["traffic"]["engine"]
    head, tail = mine["attached_tokens"], mine["prompt_tokens"] - mine["attached_tokens"]
    assert head > cell["traffic"]["shared_prefix_tokens"] and head % eng["block_size"]
    assert mine["last_positions"] <= tail <= eng["prefill_chunk_tokens"]


def test_the_mix_is_chat_closed_c32_s_behind_a_shared_prefix():
    mix, base = spec.load("traffic", "chat_prefix_closed_c32"), spec.load(
        "traffic", "chat_closed_c32")
    assert mix["engine"] == dict(base["engine"], prefix_cache=True)
    for key in ("arrival", "output_tokens", "strata", "warmup_seconds", "trace_seconds",
                "throughput_counts", "kind"):
        assert mix[key] == base[key], key
    assert (mix["shared_prefix_tokens"], mix["prefix_groups"]) == (2048, 4)
    lengths = traffic_gen.length_cycle(mix["prompt_tokens"], mix["strata"])
    assert lengths.min() >= 2112 and lengths.max() <= 3072
    assert 100.0 * 2048 / lengths.mean() == pytest.approx(79.0, abs=0.5)
    # blocks: four shared prefixes and 32 rows' own tails and outputs fit the pool
    eng = mix["engine"]
    own = -(-(3072 - 2048 + mix["output_tokens"]["max"]) // eng["block_size"])
    shared = mix["prefix_groups"] * 2048 // eng["block_size"]
    assert (shared, own) == (512, 88) and shared + eng["slots"] * own <= eng["pool_blocks"]
    stream = traffic_gen.RequestStream(mix, 32768, seed=2147483650)
    heads = set()
    for _ in range(64):
        prompt, n_out = stream.next()
        assert 2112 <= len(prompt) <= 3072 and 32 <= n_out <= 384
        heads.add(prompt[:2048].tobytes())
    assert len(heads) == 4


def _tiny():
    cell = tiny_cell(CELL)
    t = cell["traffic"]
    assert t["engine"]["prefix_cache"] is True
    t.update(shared_prefix_tokens=16, prefix_groups=2)
    t["prompt_tokens"].update(min=24, max=60)
    # four blocks and a half attach; the 28-token tail is one chunk
    cell["correctness"].update(prompt_tokens=64, attached_tokens=36)
    return cell


def test_the_cell_runs_end_to_end_at_a_tiny_preset(capsys):
    import jax

    from bench_matrix import run

    cell = _tiny()
    ctx = context(1.0, jax.devices()[:1])
    try:
        line = run.execute(cell, ctx, FAKE_PEAKS, {"platform": "cpu", "kind": "cpu", "count": 1})
    finally:
        ctx.compiles.close()
    said = capsys.readouterr().out
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert "refused" not in said
    assert "prefill of 64 tokens through the paged cache" in said
    assert "prompt 36 tokens were attached from the prefix index" in said
    from bench_matrix.runners import serve, serve_prefix
    assert serve._check is serve_prefix._compare  # the stand-in is gone again


# --- the check that attaches, and controls it must fail ------------------------------

def _shifted(attach):
    def shifted(self, slot, blocks):
        blocks = list(blocks)
        return attach(self, slot, blocks[1:] + blocks[:1])
    return shifted


CONTROLS = (
    "as_it_is",
    # the head's blocks in another order: every attached key at a wrong position
    "attached_blocks_shifted_by_one",
    # the index gives nothing back: the comparison passes on the miss path, the check does not
    "nothing_attached",
)


@pytest.mark.parametrize("case", sorted(CONTROLS))
def test_the_check_attaches_and_a_wrong_attach_fails_it(case, monkeypatch, capsys):
    import jax

    from bench_matrix import modelglue
    from bench_matrix.runners import serve_prefix
    from pytorch_distributed_example_tpu.serve import ServeEngine
    from pytorch_distributed_example_tpu.serve.cache import PagedKVCache
    from pytorch_distributed_example_tpu.serve.prefix import PrefixIndex

    cell = _tiny()
    config, eng = cell["config"], dict(cell["traffic"]["engine"])
    model = modelglue.build_model(config, eng.pop("max_seq_len"), remat=False)
    variables = modelglue.make_variables(model, config, 3)
    engine = ServeEngine(model, variables, **eng)
    if case == "attached_blocks_shifted_by_one":
        monkeypatch.setattr(PagedKVCache, "attach_prefix", _shifted(PagedKVCache.attach_prefix))
    elif case == "nothing_attached":
        monkeypatch.setattr(PrefixIndex, "match", lambda self, scope, tokens: ([], 0))
    ctx = context(1.0, jax.devices()[:1], seed=2147483999)
    ok = serve_prefix._check(cell, ctx, engine, variables)
    said = capsys.readouterr().out
    assert ok is (case == "as_it_is"), said
    attached = 0 if case == "nothing_attached" else 36
    assert f"prompt {attached} tokens were attached" in said
    if case == "as_it_is":
        # what ran: the half block the match ends in was copied before the first write
        assert engine.cache.cow_copies >= 1
