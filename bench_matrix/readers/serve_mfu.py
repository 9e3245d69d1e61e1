"""The share of the chip's peak that the whole serve window used: model
FLOPs of the tokens the engine COMPUTED between the window's first and last
step, over the window's seconds times chips times the peak. Computed is what
the runner kept of every call of the window from `engine.last_step`: every
real token of every prefill chunk dispatched (padding is not a token; a
prompt token attached from the prefix cache was not computed and is in no
chunk) and every row of every decode step, each at the forward count of the
configuration's glue at the token's own context (`modelglue.forward_flops`:
active parameters only, attention by the keys it attends, nothing
recomputed). An end-to-end utilization, not a kernel's roofline share: it
still bounds a claim when a kernel is taken out or fused and its own
roofline falls silent. None where the runner kept no such record."""

from .. import modelglue


def read(args, env):
    done, window = env.samples.get("computed"), env.samples.get("window")
    if not done or not window:
        return None
    flops_of = modelglue.forward_flops(env.cell["config"])
    chunks = sum(flops_of(start, tokens) for start, tokens in done["chunks"])
    decode = sum(flops_of(keys - 1, 1) for keys in done["decode_keys"])
    span_s = window[1] - window[0]
    peak = env.chips * env.peaks["bf16_flops_per_s"]
    env.say(
        f"serve mfu: {sum(t for _, t in done['chunks'])} tokens in {len(done['chunks'])} "
        f"prefill chunks ({chunks:.4e} model FLOPs) and {len(done['decode_keys'])} decoded "
        f"tokens ({decode:.4e}) in {span_s:.2f} s: {(chunks + decode) / span_s:.4e} FLOP/s "
        f"over {env.chips} x {env.peaks['bf16_flops_per_s']:.3e}")
    return 100.0 * (chunks + decode) / span_s / peak
