"""Share of device busy time in operations whose name matches
`args.pattern`, mean over devices."""

from ..reduce import xplane


def read(args, env):
    if env.trace is None or not env.trace.devices:
        return None
    hit = xplane.time_matching(env.trace, args["pattern"])
    if not any(h["events"] for h in hit.values()):
        return None
    busy = xplane.busy(env.trace)["busy_s"]
    shares = [hit[d]["seconds"] / busy[d] for d in busy if busy[d] > 0]
    return 100.0 * sum(shares) / len(shares)
