"""Open-loop arrival traces on a virtual clock, for the autoscaler's tests.

`make_trace` draws a bursty multi-tenant trace (trough -> `peak_x` x
trough -> trough) that the same seed replays exactly; `replay` submits
it to a router whose engines, controller and clock cell share one
virtual clock, so arrivals never wait on completions and every
controller decision is deterministic.
"""

from __future__ import annotations

import math

PREAMBLE = 12  # shared per-tenant prefix tokens (the affinity payload)
SUFFIX = (4, 9)  # unique per-request tail tokens (half-open)
NEW = (3, 8)  # decode budgets (half-open)


def make_trace(
    seed: int,
    duration_s: float,
    peak_x: float,
    requests: int,
    tenants: int,
    vocab: int,
    gold_frac: float = 0.5,
):
    """Deterministic open-loop trace: `requests` arrival events over
    `duration_s` virtual seconds from the diurnal rate

        rate(t) = base * (1 + (peak_x - 1) * sin(pi * t / D)^2)

    (trough at both ends, one `peak_x`-times-trough peak mid-trace),
    sampled by inverse-CDF so the SAME seed replays the SAME
    timestamps. Each event carries tenant, class, prompt (tenant
    preamble + unique suffix), budget, and its own sampling seed —
    everything a replay (or a post-resize re-replay) needs."""
    import numpy as np

    gen = np.random.default_rng(seed)
    # inverse-CDF sampling of the normalized rate density on a grid
    grid = np.linspace(0.0, duration_s, 4096)
    dens = 1.0 + (peak_x - 1.0) * np.sin(math.pi * grid / duration_s) ** 2
    cum = np.concatenate([[0.0], np.cumsum((dens[1:] + dens[:-1]) / 2)])
    cum /= cum[-1]
    arrivals = np.sort(np.interp(gen.uniform(size=requests), cum, grid))
    preambles = [
        gen.integers(0, vocab, (PREAMBLE,)).astype(np.int32)
        for _ in range(tenants)
    ]
    events = []
    for i, arr in enumerate(arrivals):
        ten = int(gen.integers(0, tenants))
        suffix = gen.integers(
            0, vocab, (int(gen.integers(*SUFFIX)),)
        ).astype(np.int32)
        events.append(
            {
                "arrival": float(arr),
                "rid": f"r{i}",
                "tenant": f"ten{ten}",
                "klass": "gold" if gen.uniform() < gold_frac else "bronze",
                "prompt": np.concatenate([preambles[ten], suffix]),
                "budget": int(gen.integers(*NEW)),
                "seed": i,
            }
        )
    return events


def replay(
    events,
    router,
    clock_cell,
    step_cost_s: float,
    autoscaler=None,
    poll_every_s: float = 0.5,
    max_steps: int = 200_000,
):
    """Open-loop replay on the virtual clock: submit everything whose
    timestamp has passed, step the gang once (one step-time regardless
    of width — replicas are parallel hardware), advance time, poll the
    controller on its interval. Runs until the trace is exhausted AND
    the gang drains. Returns the number of router steps taken."""
    i = 0
    next_poll = 0.0
    steps = 0
    while True:
        now = clock_cell[0]
        while i < len(events) and events[i]["arrival"] <= now:
            ev = events[i]
            router.submit(
                ev["prompt"],
                ev["budget"],
                rid=ev["rid"],
                seed=ev["seed"],
                arrival_time=ev["arrival"],
                tenant=ev["tenant"],
                klass=ev["klass"],
            )
            i += 1
        if autoscaler is not None and now >= next_poll:
            autoscaler.poll()
            next_poll = now + poll_every_s
        busy = router.step()
        clock_cell[0] += step_cost_s
        steps += 1
        if steps > max_steps:
            raise RuntimeError(
                f"harness did not drain within {max_steps} steps "
                f"(submitted {i}/{len(events)})"
            )
        if i >= len(events) and not busy:
            return steps
