"""The linear layers' decode recurrence as a share of its roofline in the
decode steps of the traced slice: the least time the chip could take to
read and write the recurrent state of every row that decoded in those steps
(with its conv tail and its vectors) and to do the update's FLOPs, as the
configuration's glue counts them (`recurrence_decode_call`, a step's live
rows being the rows the runner kept keys for), over the device time of the
operations under `args.scope` in the program `args.program`. Memory-bound.
No number (the key is left out, never 0) without a trace, where the glue
has no such count or the program no such scope (a program from before the
linear layers), or unless the runs of the program in the trace and the
steps the runner kept pair one to one: bytes and time would not be of the
same steps."""

import re

import numpy as np

from .. import flops, modelglue
from ..reduce import scopes
from .scope_time import _scopes


def read(args, env):
    kept = env.samples.get("decode_keys")
    sc = _scopes(env)
    if sc is None or not kept:
        return None
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "recurrence_decode_call"):
        return None
    ms = scopes.time_in(sc, args["program"], args["scope"])
    if not ms:
        return None
    runs = [sum(1 for r in rs if re.search(args["program"], r[0]))
            for rs in sc.runs.values() if rs]
    if any(n != len(kept) for n in runs):
        env.say(f"recurrence roofline: {runs} runs of the program in the trace "
                f"against {len(kept)} steps kept: no number")
        return None
    dtype = cfg["dtype"]
    itemsize = lambda key: np.dtype(modelglue.DTYPES[dtype[key]]).itemsize
    calls = [glue.recurrence_decode_call(cfg, len(step), itemsize("recurrent_state"),
                                         itemsize("activations")) for step in kept]
    need_bytes = sum(c["bytes"] for c in calls)
    need_flops = sum(c["flops"] for c in calls)
    least = flops.roofline_seconds(need_flops, need_bytes, env.peaks)
    spent_s = ms / 1e3 * len(kept)
    env.say(
        f"decode recurrence: {spent_s:.4f} s in {len(kept)} steps (mean "
        f"{np.mean([len(step) for step in kept]):.1f} live rows), needed "
        f"{need_bytes:.3e} bytes of state, conv tails and vectors and {need_flops:.3e} "
        f"FLOPs, {least['bound']}-bound, least {least['seconds']:.4f} s, read and "
        f"wrote {need_bytes / spent_s:.3e} bytes/s"
    )
    return 100.0 * least["seconds"] / spent_s
