"""Host time per harness span: the seconds of the traced slice in which no
device ran an operation, over the number of spans named `args.span` in it,
in milliseconds. A mean: see `xplane.idle_per_span` for why not a median."""

from ..reduce import xplane


def read(args, env):
    if env.trace is None:
        return None
    idle = xplane.idle_per_span(env.trace, args["span"])
    return None if idle is None else 1e3 * idle
