"""The flash kernels' share of their roofline: the least time the chip
could take for the calls' operations and bytes (from shapes, by
`flops.flash_call`) over the kernels' time in the trace. One training step
runs, per layer and per device, the forward kernel once (twice under remat)
and the backward kernels once, on this device's share of the batch."""

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
    fwd = 2 if s["remat"] else 1
    per_step = cfg["num_hidden_layers"]
    need_flops = s["trace_steps"] * per_step * (
        fwd * call["forward_flops"] + call["backward_flops"])
    need_bytes = s["trace_steps"] * per_step * (
        fwd * call["forward_bytes"] + call["backward_bytes"])
    least = flops.roofline_seconds(need_flops, need_bytes, env.peaks)
    kernel_s = sum(seconds) / len(seconds)
    env.say(
        f"flash kernels: {kernel_s:.4f} s a device in the slice "
        f"({[h['events'] for h in hit.values()]} events), needed "
        f"{need_flops:.3e} FLOPs and {need_bytes:.3e} bytes, {least['bound']}-bound, "
        f"achieved {need_flops / kernel_s:.3e} FLOP/s"
    )
    return 100.0 * least["seconds"] / kernel_s
