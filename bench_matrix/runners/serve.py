"""Runs a serving cell: one `ServeEngine` on one chip, driven from this
thread by the load the traffic file describes.

Closed loop (`arrival.mode == "closed"`): `clients` callers, each sends its
next request when its last completes. Open loop (`"open"`): requests arrive
on a seeded schedule whatever the engine does, are timed from when they were
due, and the generator's lateness is printed.

Token times: the engine stamps a request's first token itself
(`Completion.ttft_s`); later tokens are stamped by this harness's clock at
the return of each `engine.step()`, which gives every decoding request one
token after a host readback. A request that finishes in step j with n
tokens got tokens 2..n in the n-1 consecutive steps ending at j. A request
that was preempted and requeued breaks that mapping and is left out of the
inter-token sample (and counted).
"""

from __future__ import annotations

import numpy as np

from .. import correctness, flops, modelglue, traffic_gen
from ..context import Context, Result


class _PrefillProbe:
    """Keeps the last prefill chunk's (start, logits): the engine exposes
    tokens only, and the check compares logits."""

    def __init__(self, program):
        self.program = program
        self.last = None

    def __call__(self, params, tree, chunk, bt_row, start):
        tree, logits = self.program(params, tree, chunk, bt_row, start)
        self.last = (int(start), logits)
        return tree, logits


class _DecodeProbe:
    """Keeps, for every decode step it passes on, the keys each decoding
    row attends: the host's mirror of the row's length and the token the
    step writes. NOT used by `run` any more (PR 40: the loop reads
    `engine.last_step`, and nothing here assigns to `engine._step`): it
    stays for the program's own tests that hold `engine.last_step` against
    it (`tests/test_serve_pipeline.py`, `tests/test_serve_step_record.py`),
    which a benchmark PR may not edit; it goes with them (`PERF.md` section 7)."""

    def __init__(self, engine):
        self.engine, self.program, self.keys = engine, engine._step, []

    def __call__(self, *args):
        lengths = self.engine.cache.lengths
        self.keys.append([int(lengths[s]) + 1 for s in sorted(self.engine._decoding)])
        return self.program(*args)


def _drain(engine, limit=100000):
    done = engine.run(max_steps=limit)
    out = dict(done)
    done.clear()
    return out


def _warm_shapes(engine, vocab, chunk):
    """One request per prefill program the engine can build (the buckets up
    to the chunk budget), two tokens each, so the decode step runs too."""
    for n in [b for b in engine.buckets if b <= chunk]:
        engine.submit(np.arange(n, dtype=np.int32) % vocab, 2, rid=f"warm{n}")
        _drain(engine)


def _check(cell, ctx, engine, variables):
    """Prefill through the paged cache, then decoded positions, against the
    float32 reference's full forward pass over the same tokens."""
    config, c = cell["config"], cell["correctness"]
    n_prompt, n_dec, last = c["prompt_tokens"], c["decode_positions"], c["last_positions"]
    prompt = traffic_gen.check_sequence(config["vocab_size"], ctx.seed, n_prompt)
    probe = engine._prefill_chunk = _PrefillProbe(engine._prefill_chunk)
    try:
        rid = engine.submit(prompt, n_dec + 1, rid="check")
        tokens = _drain(engine)[rid].tokens
    finally:
        engine._prefill_chunk = probe.program
    start, chunk_logits = probe.last
    if n_prompt - last < start:
        raise ValueError("the last prefill chunk does not hold the rows to check")
    got = np.asarray(chunk_logits[n_prompt - last - start:n_prompt - start])
    full = np.concatenate([prompt, np.asarray(tokens[:n_dec], np.int32)])
    want = correctness.reference_logits(config, variables, full, last + n_dec)
    out = correctness.compare(got, want[:last], c)
    gap = correctness.chosen_gap(want[last:], tokens[1:n_dec + 1])
    ctx.say_compared(
        f"correctness: prefill of {n_prompt} tokens through the paged cache vs "
        f"float32 reference, last {last}: max_rel {out['max_rel']:.3e} (limit "
        f"{c['max_rel']}), rms_rel {out['rms_rel']:.3e} (limit {c['rms_rel']}); "
        f"{n_dec} decoded positions: chosen token within {gap:.3e} of the "
        f"reference's best logit (limit {c['chosen_gap']})"
    )
    return out["ok"] and gap <= c["chosen_gap"]


class _Loop:
    """Offers the load and steps the engine; keeps every step's end time
    and every completion."""

    def __init__(self, engine, traffic, vocab, ctx):
        self.engine, self.ctx, self.clock = engine, ctx, ctx.clock
        self.arrival = traffic["arrival"]
        self.stream = traffic_gen.RequestStream(traffic, vocab, ctx.seed)
        self.step_end = []  # harness clock at each engine.step() return
        self.slots = []  # active slots the engine recorded for each step
        # what each call dispatched, from `engine.last_step`: its prefill
        # chunks (slot, start, tokens, bucket), its decoding rows' keys and,
        # while `count_distinct`, the step's `_distinct_keys`
        self.dispatched = []
        self.count_distinct = False
        # tokens so far, per step: prompts taken in plus tokens given out, and
        # tokens given out alone (see `_count`)
        self.progress = {"all": [], "generated": []}
        self._completed = {"all": 0, "generated": 0}
        self._held = np.zeros(engine.cache.lengths.shape, np.int64)
        self._slot_prompt = np.zeros_like(self._held)
        self.done = []  # dicts, one per completion, in completion order
        self.arrived = {}  # rid -> arrival time
        self.refused = []  # times of submissions the engine refused
        self.late_s = []  # open loop: how late each submission was made
        self.closed = self.arrival["mode"] == "closed"
        self.pending = []  # (due time, output cut), latest first

    def _submit(self, arrival_time, scale=1.0):
        with self.ctx.span("generator"):
            prompt, n_out = self.stream.next()
        n_out = max(2, int(n_out * scale))
        rid = f"r{self.stream.count}"
        with self.ctx.span("submit"):
            try:
                self.engine.submit(prompt, n_out, rid=rid, arrival_time=arrival_time)
            except (ValueError, RuntimeError) as e:
                self.refused.append(self.clock())
                self.ctx.say(f"refused {rid}: {e}")
                return
        self.arrived[rid] = arrival_time

    def start(self, horizon_s):
        """Lay out the arrivals that do not wait for a completion: the whole
        schedule of an open loop, or a closed loop's first round. That round
        is spread evenly over `arrival.ramp_seconds` and its outputs are cut
        to a seeded fraction: callers that all start at once would queue for
        the prefill budget as they never do again, and would finish in step."""
        now = self.clock()
        if self.closed:
            n = self.arrival["clients"]
            cut = np.random.default_rng([self.ctx.seed, 0x57A6]).uniform(0.05, 1.0, n)
            due = now + np.arange(n) * self.arrival.get("ramp_seconds", 0.0) / n
            self.pending = list(zip(due.tolist(), cut.tolist()))
        else:
            due = now + traffic_gen.open_arrivals(self.arrival, self.ctx.seed, horizon_s)
            self.pending = [(t, 1.0) for t in due.tolist()]
        self.pending.reverse()  # pop() takes the earliest

    def _offer(self):
        now = self.clock()
        while self.pending and self.pending[-1][0] <= now:
            due, cut = self.pending.pop()
            if not self.closed:
                self.late_s.append(now - due)
            self._submit(due, scale=cut)

    def step(self):
        self._offer()
        with self.ctx.span("engine.step"):
            busy = self.engine.step()
        now = self.clock()
        self.step_end.append(now)
        self.slots.append(self.engine.metrics.slots_active)
        rec = self.engine.last_step
        distinct = (self._distinct_keys(rec.decode_keys)
                    if self.count_distinct and rec.decode_keys else None)
        self.dispatched.append((rec.chunks, rec.decode_keys, distinct))
        if self.engine.completions:
            for rid, c in list(self.engine.completions.items()):
                del self.engine.completions[rid]
                self.done.append({
                    "t": now, "step": len(self.step_end) - 1,
                    "arrival": self.arrived.pop(rid), "ttft_s": c.ttft_s,
                    "tpot_s": c.tpot_s, "tokens": len(c.tokens),
                    "prompt": c.prompt_len, "requeues": c.requeues,
                    "reason": c.finish_reason,
                })
                self._completed["all"] += c.prompt_len + len(c.tokens)
                self._completed["generated"] += len(c.tokens)
                if self.closed:
                    self._submit(now)
        self._count()
        if not busy and self.pending:
            # nothing to do: wait for the next arrival
            while self.clock() < self.pending[-1][0]:
                pass

    def _distinct_keys(self, keys):
        """The keys the step just dispatched attends, a key that several
        rows' block tables hold counted once (`flops.distinct_keys`). The
        rows that decoded are the slots whose length moved in this call and
        is not 0 (a prefill that completes decodes in the same call; a
        retired or preempted slot reads 0), and their tables are what the
        step was handed: growth and copy-on-write come before a dispatch,
        and a row is freed only once it has left the decoding set. None
        where those slots' lengths are not the record's keys."""
        cache = self.engine.cache
        cur = cache.lengths.astype(np.int64)
        rows = np.flatnonzero((cur > 0) & (cur != self._held))
        if tuple(cur[rows].tolist()) != tuple(keys):
            return None
        return flops.distinct_keys(cache.block_tables[rows], cur[rows], cache.block_size)

    def _count(self):
        """Tokens so far, from the engine's own `cache.lengths`: 0 for a free
        or prefilling slot, the prompt's length when its prefill completes,
        one more after every decode step. A slot that turns positive in a
        step holds prompt + 1 (its request got its first token from the
        prefill and its second from this step's decode); a request holds all
        but the newest of its generated tokens. So a prompt counts when its
        prefill completes and a generated token when it is emitted."""
        cur = self.engine.cache.lengths.astype(np.int64)
        new = (self._held == 0) & (cur > 0)
        self._slot_prompt[new] = cur[new] - 1
        self._held = cur
        live = cur > 0
        in_flight = {
            "all": int((cur[live] + 1).sum()),
            "generated": int((cur[live] - self._slot_prompt[live] + 1).sum()),
        }
        for kind, series in self.progress.items():
            series.append(self._completed[kind] + in_flight[kind])

    def run_until(self, t_end):
        while self.clock() < t_end:
            self.step()


def inter_token_s(done, step_end):
    """Every gap between consecutive tokens of the given completions, and
    how many completions were left out because they had been requeued."""
    t = np.asarray(step_end)
    gaps, skipped = [], 0
    for d in done:
        n, j = d["tokens"], d["step"]
        if n < 2:
            continue
        if d["requeues"]:
            skipped += 1
            continue
        i = j - (n - 2)  # the step that gave token 2
        gaps.append([t[i] - (d["arrival"] + d["ttft_s"])])
        gaps.append(np.diff(t[i:j + 1]))
    return (np.concatenate(gaps) if gaps else np.zeros(0)), skipped


def run(cell: dict, ctx: Context) -> Result:
    from pytorch_distributed_example_tpu.serve import ServeEngine

    config, traffic = cell["config"], cell["traffic"]
    clock, eng = ctx.clock, dict(traffic["engine"])
    model = modelglue.build_model(config, eng.pop("max_seq_len"), remat=False)
    variables = modelglue.make_variables(model, config, ctx.seed)
    engine = ServeEngine(model, variables, clock=clock, **eng)
    ctx.say(f"weights and cache on the device at {clock() - ctx.t_start:.1f} s")
    _warm_shapes(engine, config["vocab_size"], eng["prefill_chunk_tokens"])
    ctx.say(f"programs compiled or loaded at {clock() - ctx.t_start:.1f} s")
    correct = _check(cell, ctx, engine, variables)
    ctx.say(f"reference check done at {clock() - ctx.t_start:.1f} s")

    loop = _Loop(engine, traffic, config["vocab_size"], ctx)
    trace_s = traffic["trace_seconds"] if ctx.trace_dir else 0.0
    loop.start(traffic["warmup_seconds"] + ctx.seconds + trace_s + 60.0)
    loop.run_until(clock() + traffic["warmup_seconds"])
    warm_steps, warm_done = len(loop.step_end), len(loop.done)

    # the window runs from the end of the last warm-up step to the end of
    # the first step that ends after `seconds`
    requests_before = ctx.compiles.requests
    t_open = loop.step_end[-1]
    setup_s = t_open - ctx.t_start
    loop.run_until(t_open + ctx.seconds)
    compiles = ctx.compiles.requests - requests_before
    last = len(loop.step_end) - 1
    t_close = loop.step_end[last]
    late = list(loop.late_s)

    # the traced slice holds whole steps only: everything outstanding is
    # read back before the trace starts and before it stops (`stop_trace`
    # cuts what is in flight); both lie behind the window
    first_traced = len(loop.dispatched)
    if trace_s:
        loop.count_distinct = True
        engine.flush()
        with ctx.tracing():
            loop.run_until(clock() + trace_s)
            engine.flush()

    done = [d for d in loop.done if warm_steps <= d["step"] <= last]
    good = [d for d in done if d["reason"] in ("length", "eos")]
    refused = sum(1 for t in loop.refused if t_open < t <= t_close)
    if not good:
        raise RuntimeError("no request completed inside the window")
    # tokens the engine took in and gave out between the last warm-up step
    # and the last step of the window: a prompt counts when its prefill
    # completes, a generated token when it is emitted. (The tokens of the
    # requests that COMPLETED in the window, printed beside, swing by a few
    # percent with which long prompts happen to finish inside it.)
    span_s = t_close - t_open
    counts = traffic["throughput_counts"]  # "all" or "generated"
    in_window = {k: v[last] - v[warm_steps - 1] for k, v in loop.progress.items()}
    tokens = in_window[counts]
    completed_tokens = sum(d["prompt"] + d["tokens"] for d in good)
    generated = sum(d["tokens"] for d in good)
    ttft = np.array([d["ttft_s"] for d in good])
    itl, skipped = inter_token_s(good, loop.step_end)
    step_ms = 1e3 * np.diff(loop.step_end[warm_steps - 1:last + 1])
    prompts = np.array([d["prompt"] for d in good])
    ctx.say(
        f"warm-up {warm_steps} steps, {warm_done} completions; window "
        f"{span_s:.2f} s: {last + 1 - warm_steps} steps, {in_window['all']} tokens in "
        f"and out, {in_window['generated']} of them generated ({counts!r} count); {len(good)} "
        f"completions of {completed_tokens} tokens ({generated} generated), step ms p50 "
        f"{np.median(step_ms):.1f} p90 {np.percentile(step_ms, 90):.1f}"
    )
    ctx.say(
        f"prompt tokens p10/p50/p90 {np.percentile(prompts, [10, 50, 90])}; "
        f"inter-token samples {len(itl)} (requeued requests left out: "
        f"{skipped}); preempted {engine.metrics.preempted}; generator "
        f"lateness ms p50/max "
        + (f"{1e3 * np.median(late):.2f}/{1e3 * max(late):.2f}" if late else "n/a (closed loop)")
    )
    return Result(
        correct=correct,
        attempted=len(done) + refused,
        failed=len(done) - len(good) + refused,
        metrics={
            "serve_tokens_per_s": tokens / span_s,
            "serve_itl_ms_p90": 1e3 * float(np.percentile(itl, 90)),
            "setup_s": setup_s,
        },
        samples={
            "compiles_in_window": compiles,
            "slots_active": loop.slots[warm_steps:last + 1],
            "ttft_s": ttft.tolist(),
            "tpot_s": [d["tpot_s"] for d in good if d["tokens"] > 1],
            # traced slice only: per decode step dispatched, each decoding
            # row's keys and the step's distinct keys (a shared block once)
            "decode_steps": [
                {"keys": list(keys), "distinct": distinct}
                for _, keys, distinct in loop.dispatched[first_traced:] if keys
            ],
            # what the window's calls computed: (start, tokens) of every
            # prefill chunk, the keys of every decoding row of every step
            "computed": {
                "chunks": [[c[1], c[2]] for chunks, _, _ in loop.dispatched[warm_steps:last + 1]
                           for c in chunks],
                "decode_keys": [k for _, keys, _ in loop.dispatched[warm_steps:last + 1]
                                for k in keys],
            },
            # everything the numbers above were worked out from
            "window": [t_open, t_close], "requests": loop.done,
            "step_end": loop.step_end, "progress": loop.progress,
        },
    )
