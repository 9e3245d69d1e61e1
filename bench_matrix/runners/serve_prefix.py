"""Runs a serving cell whose engine shares prompt prefixes: `runners/serve.py`
as it is, with a check that ATTACHES.

`serve._check` submits one seeded random prompt, which matches nothing in
the prefix index, so in a cell with the prefix cache on it compares the miss
path alone. Here a first request (not compared) prefills and indexes a prompt
that opens with the check prompt's first `correctness.attached_tokens`
tokens and goes on with a tail of its own; then `serve._check` runs the
check prompt, which now matches that head in the index, adopts its blocks
(`cache.attach_prefix`), copies the block the match ends inside before its
first write (`attached_tokens` is not a whole number of blocks) and prefills
only its own tail. What is compared, and the limits, are `serve._check`'s:
the tail chunk's last `last_positions` logit rows and `decode_positions`
decoded tokens against the float32 reference's forward pass over the WHOLE
sequence, so a wrong attached table, a stale shared block or a wrong
position after the attach reads as not correct. The check fails too where
the index did not give the head back.
"""

from __future__ import annotations

import numpy as np

from .. import traffic_gen
from . import serve

_compare = serve._check  # the accepted comparison, which `run` below stands in front of


def _check(cell, ctx, engine, variables):
    c, vocab = cell["correctness"], cell["config"]["vocab_size"]
    n_prompt, n_head = c["prompt_tokens"], c["attached_tokens"]
    head = traffic_gen.check_sequence(vocab, ctx.seed, n_prompt)[:n_head]
    tail = traffic_gen.check_sequence(vocab, ctx.seed + 1, n_prompt - n_head)
    engine.submit(np.concatenate([head, tail]), 2, rid="check_head")
    serve._drain(engine)
    reused = engine.prefix.stats()["prefix_tokens_reused"]
    ok = _compare(cell, ctx, engine, variables)
    attached = engine.prefix.stats()["prefix_tokens_reused"] - reused
    ctx.say_compared(
        f"correctness: of the check's {n_prompt}-token prompt {attached} tokens were "
        f"attached from the prefix index (at least {n_head}: the head a request before "
        f"it prefilled and indexed)"
    )
    return ok and attached >= n_head


def run(cell, ctx):
    # `serve.run` finds its check by name in its own module
    serve._check = _check
    try:
        return serve.run(cell, ctx)
    finally:
        serve._check = _compare
