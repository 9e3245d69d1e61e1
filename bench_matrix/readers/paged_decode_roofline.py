"""Paged decode attention's share of its roofline in the decode steps of the
traced slice: the least time the chip could take to read the live K/V those
steps attend and to do their scores and sums (`flops.paged_decode_call`, from
what the runner kept of every step it dispatched: each decoding row's keys
for the FLOPs, the step's DISTINCT keys for the bytes) over the device time
of the operations under `args.scope` in the WHOLE runs of `args.program`
that pair with a kept step (`readers/latent_steps.py`): whatever implements
the attention, a kernel, its work lists, a gather. Only rows that DECODE in
a step count: a parked or mid-prefill lane attends nothing. Memory-bound.
The key is left out (never 0) where there is nothing to read: no trace, no
step kept, a program without the scope, no whole run."""

from .. import flops, modelglue
from . import latent_steps


def read(args, env):
    cfg = env.cell["config"]
    layers = cfg["num_hidden_layers"]
    itemsize = modelglue.itemsize(cfg, "kv_cache")

    def count(step):
        if step["distinct"] is None:
            return None
        call = flops.paged_decode_call(
            step["keys"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], itemsize, distinct=step["distinct"])
        return {k: layers * v for k, v in call.items()}

    return latent_steps.read(args, env, latent_steps.decode_steps(env), count,
                             "paged decode attention")
