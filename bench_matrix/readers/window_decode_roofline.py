"""The paged decode attention kernel's share of its roofline in a model
whose layers differ: as `paged_decode_roofline`, with the keys a layer
attends and its query heads taken from the configuration's pattern by its
glue (`window_decode_call`: a window layer reads min(keys, window) keys of a
row). One engine step calls the kernel once a layer; a slice whose kernel
calls are not that for every step kept gives no number."""

import numpy as np

from .. import flops, modelglue
from ..reduce import xplane


def read(args, env):
    steps = env.samples.get("decode_keys")
    if env.trace is None or not steps:
        return None
    hit = [h for h in xplane.time_matching(env.trace, args["pattern"]).values()
           if h["events"]]
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hit or not hasattr(glue, "window_decode_call"):
        return None
    layers = cfg["num_hidden_layers"]
    events = [h["events"] for h in hit]
    if any(e != len(steps) * layers for e in events):
        env.say(f"windowed decode kernel: {events} calls in the slice against "
                f"{len(steps)} decode steps x {layers} layers: no number")
        return None
    itemsize = np.dtype(modelglue.DTYPES[cfg["dtype"]["kv_cache"]]).itemsize
    calls = [glue.window_decode_call(cfg, keys, itemsize) for keys in steps]
    need_bytes = sum(c["bytes"] for c in calls)
    need_flops = sum(c["flops"] for c in calls)
    least = flops.roofline_seconds(need_flops, need_bytes, env.peaks)
    kernel_s = sum(h["seconds"] for h in hit) / len(hit)
    env.say(
        f"windowed decode kernel: {kernel_s:.4f} s in {events[0]} calls "
        f"({len(steps)} steps), needed {need_bytes:.3e} bytes of attended K/V "
        f"and {need_flops:.3e} FLOPs, {least['bound']}-bound, read "
        f"{need_bytes / kernel_s:.3e} bytes/s"
    )
    return 100.0 * least["seconds"] / kernel_s
