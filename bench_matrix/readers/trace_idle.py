"""Share of the traced slice in which no operation ran on a device: the
mean over the cell's devices; the worst is printed."""

from ..reduce import xplane


def read(args, env):
    if env.trace is None or not env.trace.devices:
        return None
    idle = xplane.busy(env.trace)["idle_share"]
    worst = max(idle, key=idle.get)
    env.say(f"device idle share: worst {100 * idle[worst]:.2f} % on {worst}")
    return 100.0 * sum(idle.values()) / len(idle)
