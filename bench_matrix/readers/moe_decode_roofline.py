"""The sparse MLPs' share of their roofline in the decode steps of the
traced slice: the least time the chip could take to read the weights of the
experts HIT in those steps (with each layer's shared expert and router) and
to do their FLOPs, as the configuration's glue counts them
(`moe_decode_call`) from the `serve:moe_step` annotations, over the device
time of the operations under `args.scope` in the program `args.program`.
No number unless annotations, runs of the program in the trace and the
steps the runner kept pair one to one: bytes and time would not be of the
same steps. It also says what share of the trace's device time no scope
claims: the grouped products are most of this program, and a kernel that
lost its path would leave `args.scope` reading the small rest."""

import re

import numpy as np

from .. import flops, modelglue
from ..reduce import scopes
from . import moe_steps
from .scope_time import _scopes


def read(args, env):
    got = moe_steps.steps(env)
    sc = _scopes(env)
    kept = env.samples.get("decode_keys")
    if not got or sc is None or not kept:
        return None
    ms = scopes.time_in(sc, args["program"], args["scope"])
    if ms is None:
        return None
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "moe_decode_call"):
        return None
    runs = [sum(1 for r in rs if re.search(args["program"], r[0]))
            for rs in sc.runs.values() if rs]
    if any(n != len(got) for n in runs) or len(kept) != len(got):
        env.say(f"moe roofline: {len(got)} annotated steps, {runs} runs of the "
                f"program in the trace, {len(kept)} steps kept: no number")
        return None
    itemsize = np.dtype(modelglue.DTYPES[cfg["dtype"]["weights"]]).itemsize
    calls = [glue.moe_decode_call(cfg, s["rows"], s["assignments"], s["experts_hit"],
                                  itemsize) for s in got]
    need_bytes = sum(c["bytes"] for c in calls)
    need_flops = sum(c["flops"] for c in calls)
    least = flops.roofline_seconds(need_flops, need_bytes, env.peaks)
    spent_s = ms / 1e3 * len(got)
    env.say(
        f"sparse MLPs in decode: {spent_s:.4f} s in {len(got)} steps (mean "
        f"{np.mean([s['rows'] for s in got]):.1f} live rows, "
        f"{np.mean([np.mean(s['experts_hit']) for s in got]):.1f} experts hit a "
        f"layer), needed {need_bytes:.3e} bytes of weights and {need_flops:.3e} "
        f"FLOPs, {least['bound']}-bound, read {need_bytes / spent_s:.3e} bytes/s; "
        f"{100 * scopes.unscoped_share(sc):.2f} % of the slice's device time is under no scope"
    )
    return 100.0 * least["seconds"] / spent_s
