"""The hyper-connection maps' share of their roofline in the prefill chunks
of the traced slice: the least time the chip could take to move the bytes
the maps' EQUATIONS need for each chunk's REAL tokens (the configuration's
glue counts them, `hc_call`, from the tokens each `serve:prefill_chunk`
annotation says: a sublayer reads the streams, writes the mixture, reads the
sublayer's output, reads the streams again and writes the new ones, whatever
implements that), over the device time of the operations under `args.scope`
in the WHOLE runs of `args.program` (every bucket's program has the name)
that pair with an annotated chunk (`readers/latent_steps.py`'s pairing of
dispatch order with run order). Memory-bound; a chunk's padding, a second
read of the streams inside one half of a map and the chain of small
reductions read as lost share. The maps are many operations a run and their
number differs between the buckets' programs, so a run is whole when it
holds as many of them as the most any run of ITS program holds (a run cut
by the edge of the trace holds fewer, or leaves no event on the modules
line). The key is left out (never 0) where there is nothing to read: no
trace, no annotations, no `hc_call` in the glue, no such scope in the
program (a program without residual streams)."""

import re

import numpy as np

from .. import flops, modelglue
from ..reduce import scopes
from . import latent_steps
from .scope_time import _scopes


def whole_runs(sc: scopes.Scopes, program: str, scope: str):
    """Per device, the device seconds of the operations under `scope` in
    every whole run of the programs named `program`, in run order:
    [(device, [seconds a whole run], runs seen)]."""
    prog_rx, scope_rx = re.compile(program), re.compile(scope)
    out = []
    for device, runs in sc.runs.items():
        mine = [r for r in runs if prog_rx.search(r[0])]
        hits = [o for o in sc.ops.get(device, [])
                if scope_rx.search("/".join(scopes.names(o[0])[0]))]
        if not mine or not hits:
            continue
        inside = [
            [o[3] for o in hits if o[1] == pid and start <= o[2] <= start + dur]
            for _, pid, start, dur in mine
        ]
        most = {}
        for (_, pid, _, _), ops in zip(mine, inside):
            most[pid] = max(most.get(pid, 0), len(ops))
        whole = [sum(ops) / 1e12 for (_, pid, _, _), ops in zip(mine, inside)
                 if ops and len(ops) == most[pid]]
        out.append((device, whole, len(mine)))
    return out


def read(args, env):
    glue = modelglue.glue(env.cell["config"])
    if not hasattr(glue, "hc_call"):
        return None
    notes = latent_steps.annotations(env, args["annotation"])
    sc = _scopes(env)
    if not notes or sc is None:
        return None
    cfg = env.cell["config"]
    itemsize = np.dtype(modelglue.DTYPES[cfg["dtype"]["activations"]]).itemsize
    shares = []
    for device, whole, seen in whole_runs(sc, args["program"], args["scope"]):
        seconds, kept = latent_steps.paired(whole, notes)
        if not seconds:
            continue
        calls = [glue.hc_call(cfg, note["tokens"], itemsize) for note in kept]
        need_bytes = sum(c["bytes"] for c in calls)
        need_flops = sum(c["flops"] for c in calls)
        least = flops.roofline_seconds(need_flops, need_bytes, env.peaks)
        spent = sum(seconds)
        env.say(
            f"hyper-connection maps on {device}: {len(notes)} annotated chunks, {seen} runs "
            f"of the program in the slice, {len(whole)} of them whole, {len(kept)} paired; "
            f"the maps took {spent:.4f} s in them, needed {need_bytes:.3e} bytes of streams "
            f"and {need_flops:.3e} FLOPs, {least['bound']}-bound, least "
            f"{least['seconds']:.4f} s ({need_bytes / spent:.3e} bytes/s)")
        shares.append(100.0 * least["seconds"] / spent)
    return sum(shares) / len(shares) if shares else None
