"""The linear layers' decode recurrence as a share of its roofline in the
decode steps of the traced slice: the least time the chip could take to
read and write the recurrent state of every row that decoded in those steps
(with its conv tail and its vectors) and to do the update's FLOPs, as the
configuration's glue counts them (`recurrence_decode_call`, a step's live
rows being the rows the runner kept keys for), over the device time of the
operations under `args.scope` in the WHOLE runs of `args.program` that pair
with a kept step (`readers/latent_steps.py`). Memory-bound. No number (the
key is left out, never 0) without a trace, where the glue has no such count
or the program no such scope (a program from before the linear layers), or
where no whole run pairs with a step."""

from .. import modelglue
from . import latent_steps


def read(args, env):
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "recurrence_decode_call"):
        return None
    state = modelglue.itemsize(cfg, "recurrent_state")
    act = modelglue.itemsize(cfg, "activations")
    return latent_steps.read(
        args, env, latent_steps.decode_steps(env),
        lambda step: glue.recurrence_decode_call(cfg, len(step["keys"]), state, act),
        "decode recurrence")
