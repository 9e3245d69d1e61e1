"""What the serve engine wrote on the host line of the trace for every
decode step of a model with sparse layers: one `serve:moe_step` annotation
a step with the live rows, the assignments computed over all sparse layers
and, per sparse layer, the distinct experts with at least one row
(`hit0`, `hit1`, ...). `args.stat` names what to make of them:
`experts_hit_mean` is the mean over the slice's steps and layers. None
without a traced slice or where the program wrote no such annotation (a
model without sparse layers, a program from before the counters existed).
"""

import os

from ..reduce import xplane

NAME = "serve:moe_step"
_PARSED = {}  # trace file -> steps: one parse a process


def steps(env):
    """[{"rows", "assignments", "experts_hit": [per sparse layer]}], one per
    decode step of the traced slice, in time order; None without a trace."""
    from jax.profiler import ProfileData

    from .. import run

    name = env.cell.get("name")
    if env.trace is None or not name:
        return None
    try:
        path = xplane.find(os.path.join(run.OUT_DIR, "trace", name))
    except FileNotFoundError:
        return None
    if path not in _PARSED:
        _PARSED.clear()
        found = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.split("#")[0] == NAME:
                        found.append((int(ev.start_ns), parse(ev)))
        _PARSED[path] = [s for _, s in sorted(found, key=lambda f: f[0])]
    return _PARSED[path]


def parse(event) -> dict:
    """The annotation's arguments, from the event's stats or, where the
    profiler left them in the name (`name#k=v,k=v#`), from there."""
    args = {str(k): v for k, v in event.stats}
    if "#" in event.name:
        for pair in event.name.split("#")[1].split(","):
            key, _, value = pair.partition("=")
            args.setdefault(key, value)
    hit = sorted((int(k[3:]), int(v)) for k, v in args.items()
                 if k.startswith("hit") and k[3:].isdigit())
    return {"rows": int(args["rows"]), "assignments": int(args["assignments"]),
            "experts_hit": [h for _, h in hit]}


def read(args, env):
    got = steps(env)
    if not got:
        return None
    if args["stat"] != "experts_hit_mean":
        raise ValueError(f"unknown statistic {args['stat']!r}")
    per_step = [sum(s["experts_hit"]) / len(s["experts_hit"]) for s in got]
    return float(sum(per_step) / len(per_step))
