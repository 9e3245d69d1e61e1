"""Model FLOP/s utilization: tokens per second times the model's FLOPs per
token from shapes (recomputed operations do not count), over chips times
the peak. An end-to-end utilization, not a kernel's roofline share."""

from .. import flops


def read(args, env):
    s = env.samples
    if "step_s" not in s:
        return None
    per_token = flops.train_flops_per_token(env.cell["config"], s["seq"])
    rate = s["tokens_per_step"] / s["step_s"] * per_token
    env.say(f"mfu: {per_token:.4e} model FLOPs a token, {rate:.4e} FLOP/s over "
            f"{env.chips} x {env.peaks['bf16_flops_per_s']:.3e}")
    return 100.0 * rate / (env.chips * env.peaks["bf16_flops_per_s"])
