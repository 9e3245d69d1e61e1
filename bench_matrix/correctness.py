"""The comparison that decides `correct`: the system's logits against the
plain float32 reference on one seeded sequence."""

from __future__ import annotations

import numpy as np

from . import modelglue, spec


def reference_logits(config: dict, variables, tokens, last: int, device=None):
    """(last, vocab) float32 logits from the configuration's reference."""
    ref = spec.resolve(config["reference"] + ":logits")
    emb, layers, norm, w_out = modelglue.reference_parts(variables, device)
    return np.asarray(ref(tokens, emb, layers, norm, w_out, config, last=last))


def compare(got, want, limits: dict) -> dict:
    """Worst error over the logit range, and RMS error over RMS logit;
    `ok` when both are inside `limits`."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise ValueError(f"logit shapes differ: {got.shape} vs {want.shape}")
    diff = got - want
    max_rel = float(np.abs(diff).max() / np.abs(want).max())
    rms_rel = float(np.sqrt((diff ** 2).mean() / (want ** 2).mean()))
    ok = bool(
        np.isfinite(got).all() and max_rel <= limits["max_rel"]
        and rms_rel <= limits["rms_rel"]
    )
    return {"max_rel": max_rel, "rms_rel": rms_rel, "ok": ok}


def chosen_gap(reference_rows, chosen) -> float:
    """For greedy decoding, where the system's logits are not exposed: how
    far below the reference's best logit the system's chosen token sits, as
    a share of the reference's logit range, worst over positions. A rounding
    flip between near-equal logits gives a small gap; a token computed from
    a wrong cache row sits anywhere in the range."""
    ref = np.asarray(reference_rows, np.float32)
    chosen = np.asarray(chosen)
    picked = ref[np.arange(len(chosen)), chosen]
    return float(((ref.max(axis=1) - picked) / np.abs(ref).max()).max())
