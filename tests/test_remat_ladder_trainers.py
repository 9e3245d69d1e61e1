"""Every rung of `utils/remat.py`'s ladder trains as no remat does: a toy
dense-GQA model and a toy patterned model, under `make_train_step` at world
1 and over the CPU mesh and under `fully_shard(...).make_train_step`. The
CPU reports no memory limit; `force` steers the one function that asks the
device and cuts the ladder at the rung wanted, and the fit itself runs as
on a chip (lower, compile, the compiler's count)."""

import numpy as np
import pytest

from pytorch_distributed_example_tpu.utils import remat
from tests._remat_toys import MODELS, build, force, run

RUNGS = range(len(remat.LADDER))

_reference = {}


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("trainer", ["ddp_world1", "ddp_mesh", "fsdp"])
@pytest.mark.parametrize("kind", sorted(MODELS))
def test_every_rung_trains_as_no_remat_does(kind, trainer, rung, world, monkeypatch):
    """Loss and gradients (one SGD step at rate 1: the new parameters are
    the old less the gradient): saved and recomputed values are the same
    numbers."""
    if (kind, trainer) not in _reference:
        _, step, params, opt_state, x, _ = build(kind, trainer, False, world)
        _reference[kind, trainer] = run(step, params, opt_state, x)
        assert step.remat_plan is None  # no limit reported, no plan
    want_loss, want_params = _reference[kind, trainer]
    force(monkeypatch, rung)
    _, step, params, opt_state, x, _ = build(kind, trainer, True, world)
    loss, new_params = run(step, params, opt_state, x)
    plan = step.remat_plan
    assert plan.rung == rung and plan.limit_bytes == 10**15
    ((r, held),) = plan.held  # the first rung tried fitted
    assert r == rung and 0 < held < plan.budget_bytes
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    np.testing.assert_allclose(new_params, want_params, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("trainer", ["ddp_world1", "ddp_mesh", "fsdp"])
def test_a_model_without_per_block_remat_has_nothing_to_fit(trainer, world, monkeypatch):
    """Under a limit too: no block reads the rung, the first program
    compiled is the step, and there is no plan to show."""
    monkeypatch.setattr(remat, "device_limit_bytes", lambda devices: 1)
    _, step, params, opt_state, x, _ = build("dense_gqa", trainer, False, world)
    loss, _ = run(step, params, opt_state, x)
    assert np.isfinite(loss) and step.remat_plan is None
