"""What the two latent roofline readers share: the host annotations the
serve engine writes at every dispatch (`serve:decode_step`: rows, keys;
`serve:prefill_chunk`: slot, start, tokens, bucket), the device time of a
kernel's calls in every WHOLE run of a program, and the pairing of the two.

The engine keeps one call's device work in flight, and the runner starts
and stops the trace between two of its calls, so the slice's first and last
step are cut: a program's run may lie in the slice while its dispatch lay
before it, and the last dispatches' runs are cut off by `stop_trace` (a cut
run leaves no event on the modules line, or not all of its kernel calls).
Dispatch order is run order, so whole runs and annotated dispatches pair in
order once the unpaired end is dropped: more whole runs than annotations,
and the leading runs were dispatched before the slice; fewer, and the
trailing annotations' runs were cut. Both counts are said.
"""

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


def kernel_seconds(sc: scopes.Scopes, program: str, scope: str, calls: int):
    """Per device, the device seconds of the operations under `scope` in
    every run of the programs named `program` that holds exactly `calls` of
    them, in run order: [(device, [seconds a whole run], runs seen)]."""
    prog_rx, scope_rx = re.compile(program), re.compile(scope)
    out = []
    for device, runs in sc.runs.items():
        mine = [r for r in runs if prog_rx.search(r[0])]
        if not mine:
            continue
        hits = [o for o in sc.ops.get(device, [])
                if scope_rx.search("/".join(scopes.names(o[0])[0]))]
        whole = []
        for _, pid, start, dur in mine:
            inside = [o[3] for o in hits if o[1] == pid and start <= o[2] <= start + dur]
            if len(inside) == calls:
                whole.append(sum(inside) / 1e12)
        out.append((device, whole, len(mine)))
    return out


def paired(whole: list, notes: list):
    """(seconds, notes) of the runs and annotations that pair, in order:
    see the module's docstring for which end is dropped."""
    if len(whole) >= len(notes):
        return whole[len(whole) - len(notes):], notes
    return whole, notes[:len(whole)]


def read(args, env, count, what: str):
    """A kernel's share of its roofline over the paired steps of the slice:
    `count(config, note, itemsize)` is the glue's {"bytes", "flops"} of one
    annotated step. None (the key is left out, never 0) without a trace,
    annotations, the scope in the program, or a single pair."""
    import numpy as np

    from .. import flops, modelglue
    from .scope_time import _scopes

    notes = annotations(env, args["annotation"])
    sc = _scopes(env)
    if not notes or sc is None:
        return None
    cfg = env.cell["config"]
    per_device = kernel_seconds(sc, args["program"], args["scope"], cfg["num_hidden_layers"])
    itemsize = np.dtype(modelglue.DTYPES[cfg["dtype"]["kv_cache"]]).itemsize
    shares = []
    for device, whole, seen in per_device:
        seconds, kept = paired(whole, notes)
        if not seconds:
            continue
        calls = [count(cfg, note, itemsize) for note in kept]
        need_bytes = sum(c["bytes"] for c in calls)
        need_flops = sum(c["flops"] for c in calls)
        least = flops.roofline_seconds(need_flops, need_bytes, env.peaks)
        spent = sum(seconds)
        env.say(
            f"{what} on {device}: {len(notes)} annotated dispatches, {seen} runs of the "
            f"program in the slice, {len(whole)} of them whole, {len(kept)} paired; the "
            f"kernel took {spent:.4f} s in them, needed {need_bytes:.3e} bytes of latents "
            f"and {need_flops:.3e} FLOPs, {least['bound']}-bound, least "
            f"{least['seconds']:.4f} s ({need_flops / spent:.3e} FLOP/s, "
            f"{need_bytes / spent:.3e} bytes/s)")
        shares.append(100.0 * least["seconds"] / spent)
    return sum(shares) / len(shares) if shares else None
