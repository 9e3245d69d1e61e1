"""The flash kernels' share of their roofline: the least time the chip
could take for the operations and bytes a training step NEEDS of them (from
shapes, by `flops.flash_call`: per layer and per device one forward and one
backward pass over this device's share of the batch, whatever the step
recomputes and however many calls it makes of it) over the kernels' time in
the trace. A forward pass that a remat rung runs twice reads as lost share,
as it does in `mfu_pct`; the reader says the calls the trace holds by
kernel name, so the cause shows beside the number."""

import re
from collections import Counter

from .. import flops
from ..reduce import xplane


def read(args, env):
    s = env.samples
    if env.trace is None or "trace_steps" not in s:
        return None
    hit = xplane.time_matching(env.trace, args["pattern"])
    seconds = [h["seconds"] for h in hit.values() if h["events"]]
    if not seconds:
        return None
    cfg = env.cell["config"]
    call = flops.flash_call(
        s["global_batch"] / env.chips, s["seq"], cfg["num_attention_heads"],
        cfg["head_dim"],
    )
    passes = s["trace_steps"] * cfg["num_hidden_layers"]
    need_flops = passes * (call["forward_flops"] + call["backward_flops"])
    need_bytes = passes * (call["forward_bytes"] + call["backward_bytes"])
    least = flops.roofline_seconds(need_flops, need_bytes, env.peaks)
    kernel_s = sum(seconds) / len(seconds)
    rx = re.compile(args["pattern"])
    names = Counter(n.split(".")[0].split(" ")[0] for ev in env.trace.devices.values()
                    for n, _, _ in ev if rx.search(n))
    env.say(
        f"flash kernels: {kernel_s:.4f} s a device in the slice (calls by kernel over "
        f"{len(hit)} devices: {dict(names)}; {passes} layer passes a device needed), "
        f"needed {need_flops:.3e} FLOPs and {need_bytes:.3e} bytes, {least['bound']}-bound, "
        f"achieved {need_flops / kernel_s:.3e} needed FLOP/s"
    )
    return 100.0 * least["seconds"] / kernel_s
