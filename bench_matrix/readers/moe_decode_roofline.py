"""The sparse MLPs' share of their roofline in the decode steps of the
traced slice: the least time the chip could take to read the weights of the
experts HIT in those steps (with each layer's shared expert and router) and
to do their FLOPs, as the configuration's glue counts them
(`moe_decode_call`) from the `serve:moe_step` annotations (written when a
step is read back, one a step of the slice since the runner flushes on both
sides of the trace), over the device time of the operations under
`args.scope` in the WHOLE runs of `args.program` that pair with an annotated
step (`readers/latent_steps.py`). It also says what share of the trace's
device time no scope claims: the grouped products are most of this program,
and a kernel that lost its path would leave `args.scope` reading the small
rest. Left out (never 0) where there is nothing to read."""

import numpy as np

from .. import modelglue
from ..reduce import scopes
from . import latent_steps, moe_steps, scope_time


def read(args, env):
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "moe_decode_call"):
        return None
    itemsize = modelglue.itemsize(cfg, "weights")
    got = moe_steps.steps(env)
    share = latent_steps.read(
        args, env, got,
        lambda s: glue.moe_decode_call(cfg, s["rows"], s["assignments"], s["experts_hit"],
                                       itemsize),
        "sparse MLPs in decode")
    if share is not None:
        env.say(
            f"sparse MLPs in decode: mean {np.mean([s['rows'] for s in got]):.1f} live rows, "
            f"{np.mean([np.mean(s['experts_hit']) for s in got]):.1f} experts hit a layer; "
            f"{100 * scopes.unscoped_share(scope_time._scopes(env)):.2f} % of the slice's device time "
            "is under no scope")
    return share
