"""What the roofline readers of the serve cells share: the steps and chunks
the engine DISPATCHED in the traced slice (the runner's record of every call
of the slice, from `engine.last_step`: `samples["decode_steps"]`; or the host
annotations the engine writes at a dispatch or a read-back:
`serve:prefill_chunk`: slot, start, tokens, bucket; `serve:moe_step`), the
device time under a scope in every WHOLE run of a program, and the pairing of
the two.

The runner reads everything back (`engine.flush()`) before the trace starts
and again before it stops, so the slice holds whole steps only and runs and
dispatches pair one to one. Where they do not (a profiler that dropped an
event, a runner that did not flush: the engine keeps one call's device work
in flight, so a program's run may lie in the slice while its dispatch lay
before it, and `stop_trace` cuts the last dispatches' runs: a cut run leaves
no event on the modules line, or not all of its operations), dispatch order
is still run order: whole runs and dispatches pair in order once the
unpaired end is dropped: more whole runs than dispatches, and the leading
runs were dispatched before the slice; fewer, and the trailing dispatches'
runs were cut. Both counts are said; "no number" only where nothing pairs.
"""

import bisect
import os
import re

from ..reduce import scopes, xplane

_PARSED = {}  # (trace file, annotation) -> notes: one parse a process


def annotations(env, name: str):
    """The arguments of every `name` annotation on the host lines of the
    cell's trace, as dicts of ints, in time order; None without a trace."""
    from jax.profiler import ProfileData

    from .. import run

    cell = env.cell.get("name")
    if env.trace is None or not cell:
        return None
    try:
        path = xplane.find(os.path.join(run.OUT_DIR, "trace", cell))
    except FileNotFoundError:
        return None
    if (path, name) not in _PARSED:
        found = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.split("#")[0] == name:
                        found.append((int(ev.start_ns), parse(ev)))
        _PARSED[(path, name)] = [n for _, n in sorted(found, key=lambda f: f[0])]
    return _PARSED[(path, name)]


def parse(event) -> dict:
    """An annotation's integer arguments, from the event's stats or, where
    the profiler left them in the name (`name#k=v,k=v#`), from there."""
    args = {str(k): v for k, v in event.stats}
    if "#" in event.name:
        for pair in event.name.split("#")[1].split(","):
            key, _, value = pair.partition("=")
            args.setdefault(key, value)
    return {k: int(v) for k, v in args.items() if str(v).lstrip("-").isdigit()}


def kernel_seconds(sc: scopes.Scopes, program: str, scope: str, calls: int = None):
    """Per device, the device seconds of the operations under `scope` in
    every WHOLE run of the programs named `program`, in run order: [(device,
    [seconds a whole run], runs seen)]. A run is whole when it holds exactly
    `calls` such operations or, with `calls` None (a scope whose operations
    are many and differ between the programs of one name), as many as the
    most any run of ITS program holds: a run cut by the edge of the trace
    holds fewer, or leaves no event on the modules line."""
    prog_rx, scope_rx = re.compile(program), re.compile(scope)
    out = []
    for device, runs in sc.runs.items():
        mine = [r for r in runs if prog_rx.search(r[0])]
        hits = [o for o in sc.ops.get(device, [])
                if scope_rx.search("/".join(scopes.names(o[0])[0]))]
        if not mine or not hits:
            continue
        starts = [o[2] for o in hits]
        inside, most = [], {}
        for _, pid, start, dur in mine:
            lo, hi = bisect.bisect_left(starts, start), bisect.bisect_right(starts, start + dur)
            inside.append([o[3] for o in hits[lo:hi] if o[1] == pid])
            most[pid] = max(most.get(pid, 0), len(inside[-1]))
        whole = [sum(ops) / 1e12 for (_, pid, _, _), ops in zip(mine, inside)
                 if ops and len(ops) == (most[pid] if calls is None else calls)]
        out.append((device, whole, len(mine)))
    return out


def paired(whole: list, notes: list):
    """(seconds, notes) of the runs and annotations that pair, in order:
    see the module's docstring for which end is dropped."""
    if len(whole) >= len(notes):
        return whole[len(whole) - len(notes):], notes
    return whole, notes[:len(whole)]


def read(args, env, notes, count, what: str, calls: int = None):
    """A scope's share of its roofline over the paired steps of the slice:
    `notes` are the dispatches in order, `count(note)` the {"bytes", "flops"}
    one of them needs (None: not counted, it and its run are left out);
    `calls` as in `kernel_seconds`. None (the key is left out, never 0)
    without a trace, dispatches, the scope in the program, or a single pair."""
    from .. import flops
    from .scope_time import _scopes

    sc = _scopes(env) if notes else None
    if sc is None:
        return None
    per_device = kernel_seconds(sc, args["program"], args["scope"], calls)
    shares = []
    for device, whole, seen in per_device:
        seconds, kept = paired(whole, notes)
        if not seconds:
            env.say(f"{what} on {device}: {len(notes)} dispatches kept, {seen} runs of "
                    f"the program in the slice, {len(whole)} of them whole: no number")
            continue
        # a dispatch the runner could not count (`count` gives None) goes
        # with its run
        both = [(sec, need) for sec, need in zip(seconds, map(count, kept)) if need is not None]
        if not both:
            continue
        need_bytes = sum(need["bytes"] for _, need in both)
        need_flops = sum(need["flops"] for _, need in both)
        least = flops.roofline_seconds(need_flops, need_bytes, env.peaks)
        spent = sum(sec for sec, _ in both)
        env.say(
            f"{what} on {device}: {len(notes)} dispatches kept, {seen} runs of the "
            f"program in the slice, {len(whole)} of them whole, {len(kept)} paired, "
            f"{len(both)} counted; the scope took {spent:.4f} s in them, needed "
            f"{need_bytes:.3e} bytes and {need_flops:.3e} FLOPs, {least['bound']}-bound, least "
            f"{least['seconds']:.4f} s ({need_flops / spent:.3e} FLOP/s, "
            f"{need_bytes / spent:.3e} bytes/s)")
        shares.append(100.0 * least["seconds"] / spent)
    return sum(shares) / len(shares) if shares else None


def decode_steps(env):
    """The decode steps the runner kept for the traced slice, one dict a
    step in dispatch order ({"keys": [a decoding row's keys, ...],
    "distinct": keys read once where several rows' tables hold one block});
    None without a traced slice."""
    return env.samples.get("decode_steps") or None
