"""Collective time over the traced slice, mean over devices: `args.which`
is 'total' (time in collective operations) or 'exposed' (the part of it
during which no other operation ran on that device)."""

from ..reduce import xplane


def read(args, env):
    if env.trace is None or not env.trace.devices:
        return None
    coll = xplane.collectives(env.trace)
    window = xplane.busy(env.trace)["window_s"]
    key = {"total": "collective_s", "exposed": "exposed_s"}[args["which"]]
    vals = [c[key] for c in coll.values()]
    return 100.0 * sum(vals) / len(vals) / window
