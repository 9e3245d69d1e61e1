"""What `ServeEngine.step` says of itself on the host lines of the trace: one
`serve:step` span a call with its phases nested inside (`serve:admit`,
`serve:gauges`, `serve:prefill_tick`, `serve:decode_tick`, `serve:wait`,
`serve:book`), a zero-length `serve:admitted` an admission (`prompt`,
`attached`, `queue_us`) and a zero-length `serve:step_done` a call with the
call's counts. The spans are on the profiler's clock, the device's too, so
since the engine keeps one call's device work in flight and no idle time
shows the host's work any more, this is where it is read.

Only the `serve:step` spans that lie whole inside the slice are kept (the
slice: from the first device operation of the trace to the last; every
`serve:` event where the trace has no device line), with what starts inside
them. `args.stat` names what a metric makes of them:

    ms_per_call    (sum of `spans` - sum of `less`) / kept calls, in ms
    share_pct      100 * sum of `spans` / sum of `of`
    arg_mean       mean of `arg` over the kept `span` events, times `scale`
    arg_ratio_pct  100 * sum of `num` / sum of `den` over the kept `span` events

None (the key is left out, never 0) without a trace, or where the program
wrote no such span (a program from before the engine kept a record). The
first metric read in a process also says the phase table, the longest call
with its split and the device's five longest idle gaps, each named after
the innermost `serve:` span that covers most of it where it is long enough
(a millisecond: the two clocks agree no closer) for the name to mean
anything. The seven metrics are durations and counts on the host's clock
alone and need no alignment.
"""

import os
from collections import defaultdict

from ..reduce import intervals as iv
from ..reduce import xplane
from .latent_steps import parse

PREFIX = "serve:"
CALL, DONE = "serve:step", "serve:step_done"
PHASES = ("serve:admit", "serve:gauges", "serve:prefill_tick", "serve:decode_tick",
          "serve:wait", "serve:book")
CLOCK_SLACK_NS = 1_000_000  # how far the host's and the device's clocks of a trace disagree
UNNAMED = "shorter than the clocks agree"
_PARSED = {}  # trace file -> events: one parse a process


def parse_file(path: str) -> list:
    """[(name, start_ns, end_ns, integer arguments)] of every `serve:` event
    on the host lines of a trace file, in start order (a span before what
    it holds)."""
    from jax.profiler import ProfileData

    found = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    found.append((ev.name.split("#")[0], s, s + int(ev.duration_ns),
                                  parse(ev)))
    return sorted(found, key=lambda e: (e[1], -e[2]))


def events(env):
    """`parse_file` of the cell's trace, None without one; the first read of
    a trace that holds such events also says `table`."""
    from .. import run

    cell = env.cell.get("name")
    if env.trace is None or not cell:
        return None
    try:
        path = xplane.find(os.path.join(run.OUT_DIR, "trace", cell))
    except FileNotFoundError:
        return None
    if path not in _PARSED:
        _PARSED[path] = parse_file(path)
        if _PARSED[path]:
            for text in table(_PARSED[path], env.trace):
                env.say(text)
    return _PARSED[path]


def calls(found, slice_ns=None):
    """The `serve:step` spans that lie whole inside `slice_ns` (every one
    without it), in order, each with the events that start inside it:
    [{"start", "end", "ns": {name: summed ns}, "events": [...]}]."""
    out = []
    for name, s, e, _ in found:
        if name == CALL and (slice_ns is None or (slice_ns[0] <= s and e <= slice_ns[1])):
            out.append({"start": s, "end": e, "ns": defaultdict(int), "events": []})
    i = 0
    for ev in found:
        name, s, e, _ = ev
        if name == CALL:
            continue
        while i < len(out) and out[i]["end"] < s:
            i += 1
        if i < len(out) and out[i]["start"] <= s:
            out[i]["ns"][name] += e - s
            out[i]["events"].append(ev)
    return out


def kept(found, trace):
    """The calls of the slice: the device window where the trace has one."""
    return calls(found, trace.window() if trace is not None else None)


def stat(args, kept_calls):
    """One metric from the kept calls, or None where there is nothing of
    what it reads."""
    def total(names):
        return sum(c["end"] - c["start"] if n == CALL else c["ns"].get(n, 0)
                   for c in kept_calls for n in names)

    what = args["stat"]
    if what not in ("ms_per_call", "share_pct", "arg_mean", "arg_ratio_pct"):
        raise ValueError(f"unknown statistic {what!r}")
    if not kept_calls:
        return None
    if what == "ms_per_call":
        return (total(args["spans"]) - total(args.get("less", ()))) / 1e6 / len(kept_calls)
    if what == "share_pct":
        whole = total(args["of"])
        return 100.0 * total(args["spans"]) / whole if whole else None
    mine = [a for c in kept_calls for name, _, _, a in c["events"] if name == args["span"]]
    if what == "arg_mean":
        values = [a[args["arg"]] for a in mine if args["arg"] in a]
        return args.get("scale", 1.0) * sum(values) / len(values) if values else None
    den = sum(a.get(args["den"], 0) for a in mine)
    return 100.0 * sum(a.get(args["num"], 0) for a in mine) / den if den else None


def innermost(found, lo, hi):
    """The name of the `serve:` span that covers most of [lo, hi]: of those
    that cover at least half of it the shortest, else the one that covers
    most; 'no span' where none touches it."""
    touching = [(min(e, hi) - max(s, lo), e - s, name) for name, s, e, _ in found
                if min(e, hi) > max(s, lo)]
    if not touching:
        return "no span"
    half = [(length, name) for cover, length, name in touching if 2 * cover >= hi - lo]
    return min(half)[1] if half else max(touching)[2]


def idle_gaps(found, trace, n=5):
    """[[innermost `serve:` span, seconds]] of the longest gaps in which no
    device ran an operation (`xplane.idle_gaps` names the harness's span). A
    gap is on the device's clock and a span on the host's, and the two agree
    only to about a millisecond (`xplane.idle_per_span`), so a gap shorter
    than `CLOCK_SLACK_NS` is given its length and `UNNAMED`: which phase, a
    fraction of a millisecond long, it fell in cannot be told."""
    win = trace.window() if trace is not None else None
    if win is None:
        return []
    ops = [(s, e) for ev in trace.devices.values() for _, s, e in ev]
    holes = sorted((g for g in iv.gaps(ops, *win) if g[1] - g[0] >= 1000),
                   key=lambda g: g[0] - g[1])[:n]
    return [[innermost(found, lo, hi) if hi - lo >= CLOCK_SLACK_NS else UNNAMED,
             (hi - lo) / 1e9] for lo, hi in holes]


def table(found, trace):
    """The lines said once a trace: the phase table (ms a call, share of the
    call), the longest call with its split, the longest idle gaps."""
    mine = kept(found, trace)
    if not mine:
        return [f"engine steps: {len(found)} `serve:` events on the host lines, no "
                "whole `serve:step` span in the slice"]
    n = len(mine)
    whole = sum(c["end"] - c["start"] for c in mine)
    rows = [(p, sum(c["ns"].get(p, 0) for c in mine)) for p in PHASES]
    rest = whole - sum(ns for _, ns in rows)
    out = [f"engine steps: {n} whole `serve:step` spans in the slice, {whole / 1e6 / n:.3f} "
           f"ms a call; by phase [ms a call, % of the call]: "
           + ", ".join(f"{p[len(PREFIX):]} {ns / 1e6 / n:.3f} {100 * ns / whole:.1f}"
                       for p, ns in rows + [(PREFIX + "no_phase", rest)])]
    longest = max(mine, key=lambda c: c["end"] - c["start"])
    done = [a for name, _, _, a in longest["events"] if name == DONE]
    out.append(
        f"longest call {(longest['end'] - longest['start']) / 1e6:.3f} ms"
        + (f" (call {done[0].get('call')}: {done[0]})" if done else "") + ": "
        + ", ".join(f"{p[len(PREFIX):]} {longest['ns'].get(p, 0) / 1e6:.3f}" for p in PHASES))
    gaps = idle_gaps(found, trace)
    if gaps:
        out.append("longest device idle gaps by innermost `serve:` span [span, us] (named from "
                   f"{CLOCK_SLACK_NS / 1e3:.0f} us up): "
                   + ", ".join(f"{name} {1e6 * sec:.1f}" for name, sec in gaps))
    return out


def read(args, env):
    found = events(env)
    if not found:
        return None
    return stat(args, kept(found, env.trace))
