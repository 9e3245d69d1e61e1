"""What a per-block `jax.checkpoint` keeps (`utils/remat.py`'s ladder): the
fit alone (fake programs with a count a rung), the one function that asks
the device, the names a model binds, and what the lowered step recomputes
at the bottom and at the top of the ladder (every rung against no remat,
under each trainer: `test_remat_ladder_trainers.py`). The CPU reports no
memory limit, so a trainer here hands out the plain program and every
other test of the suite runs rung 0; these tests steer
`utils.remat.device_limit_bytes` and nothing else.
"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.ad_checkpoint import checkpoint_name

from pytorch_distributed_example_tpu.models import transformer as tfm
from pytorch_distributed_example_tpu.models.transformer import TransformerLM
from pytorch_distributed_example_tpu.utils import remat
from tests._remat_toys import MODELS, build, force

RUNGS = range(len(remat.LADDER))
TOP = len(remat.LADDER) - 1

# -- the ladder and the trace-time rung -------------------------------------------


def test_each_rung_holds_the_one_below_it():
    assert remat.LADDER[0] == () and remat.save_policy(0) is None
    for low, high in zip(remat.LADDER, remat.LADDER[1:]):
        assert set(low) < set(high)


def test_no_trainer_around_the_trace_is_rung_0():
    assert remat.rung() == 0


@pytest.mark.parametrize("rung", RUNGS)
def test_a_function_traced_at_a_rung_hands_it_to_the_model(rung):
    def local_step(x):
        return x, remat.rung()

    trace = remat.at_rung(rung)
    traced = trace(local_step)
    assert traced.__name__ == "local_step"  # the program's name stays
    assert not trace.asked
    assert traced(5) == (5, rung) and trace.asked
    assert remat.rung() == 0  # closed again
    # one inside another: the inner decides inside, the outer after it
    inner = remat.at_rung(0)
    outer = remat.at_rung(rung)(lambda: (inner(remat.rung)(), remat.rung()))
    assert outer() == (0, rung)


def test_a_trace_that_no_model_reads_is_told_apart():
    trace = remat.at_rung(2)
    assert trace(lambda x: x + 1)(1) == 2 and not trace.asked


# -- the one function that asks the device ---------------------------------------


class _Chip:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if self._stats == "not addressable":
            raise jax.errors.JaxRuntimeError(
                "UNIMPLEMENTED: MemoryStats is only supported for addressable "
                "PjRt devices")
        return self._stats


GB = 10**9


@pytest.mark.parametrize("stats,limit", [
    ([None], None),  # the CPU
    ([{}], None),
    ([{"bytes_in_use": 5}], None),  # no limit reported
    ([{"bytes_limit": 16 * GB, "bytes_in_use": 6 * GB}], 16 * GB),
    # what is resident now does not count: the compiler counts the step's own
    ([{"bytes_limit": 16 * GB, "bytes_in_use": 0}], 16 * GB),
    ([{"bytes_limit": 16 * GB}, {"bytes_limit": 15 * GB}], 15 * GB),  # the smallest
    ([{"bytes_limit": 16 * GB}, None], None),
    # a described device of a deviceless compile, another process's chip
    (["not addressable"], None),
    ([{"bytes_limit": 16 * GB}, "not addressable"], None),
    ([], None),
])
def test_the_limit_is_the_smallest_any_device_reports(stats, limit):
    assert remat.device_limit_bytes([_Chip(s) for s in stats]) == limit


def test_the_cpu_reports_no_limit():
    assert remat.device_limit_bytes(jax.devices()) is None


# -- the fit alone -----------------------------------------------------------------


class _Counts:
    """What `CompiledMemoryStats` shows of a program."""

    def __init__(self, total):
        self.argument_size_in_bytes = total - 60
        self.output_size_in_bytes = 30
        self.alias_size_in_bytes = 20
        self.temp_size_in_bytes = 40
        self.generated_code_size_in_bytes = 10


class _Program:
    """Stands for the `jax.jit` of a step: `held[r]` bytes by the compiler's
    count at rung r, an exception to raise instead, or None where the
    compiler runs out of memory."""

    def __init__(self, trace, held, reads=True):
        self.fn = trace((lambda: remat.rung()) if reads else (lambda: 0))
        self.held = held
        self.compiles = 0

    def lower(self, *args):
        self.rung = self.fn()
        return self

    def compile(self):
        self.compiles += 1
        n = self.held[self.rung]
        if n is None:
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
                "of memory in memory space hbm.")
        if isinstance(n, Exception):
            raise n
        return self

    def memory_analysis(self):
        return _Counts(self.held[self.rung])


def _fit(held, limit, reads=True):
    built = []
    program, plan = remat.fit(
        lambda trace: built.append(_Program(trace, held, reads)) or built[-1],
        limit, ())
    assert program is built[-1] and all(b.compiles == 1 for b in built)
    return program, plan, built


HELD = (400, 500, 700, 1000)
# a limit of 16 n leaves 15 n under the margin


@pytest.mark.parametrize("limit,rung", [
    (16, 0), (16 * 26, 0), (16 * 33, 0), (16 * 34, 1), (16 * 46, 1), (16 * 47, 2),
    (16 * 66, 2), (16 * 67, 3), (10**15, 3),
])
def test_the_richest_rung_the_compiler_says_fits(limit, rung):
    program, plan, built = _fit(HELD, limit)
    assert program.rung == plan.rung == rung
    # richest first, and nothing compiled below the one that fitted
    assert [b.rung for b in built] == list(range(TOP, rung - 1, -1))
    assert plan.held == tuple((r, HELD[r]) for r in range(TOP, rung - 1, -1))
    assert plan.limit_bytes == limit and plan.budget_bytes == limit - limit // 16
    assert f"rung {rung} of {TOP}" in str(plan)


def test_the_count_is_arguments_outputs_less_aliased_temporaries_and_code():
    assert remat.held_bytes(_Program(remat.at_rung(0), (777,)).lower()) == 777


def test_the_rung_never_falls_as_the_limit_grows():
    rungs = [_fit(HELD, limit)[1].rung for limit in range(16, 16 * 80, 16)]
    assert rungs == sorted(rungs) and set(rungs) == set(RUNGS)


def test_the_margin_is_a_sixteenth_of_the_limit():
    assert _fit((1, 1, 1, 1500), 1600)[1].rung == TOP
    assert _fit((1, 1, 1, 1501), 1600)[1].rung == TOP - 1


def test_rung_0_is_taken_whatever_it_counts():
    program, plan, _ = _fit((10**12, 10**12, 10**12, 10**12), 1600)
    assert (program.rung, plan.rung) == (0, 0) and len(plan.held) == 4


def test_a_rung_the_compiler_cannot_place_is_a_rung_that_does_not_fit():
    program, plan, _ = _fit((400, 500, None, None), 10**15)
    assert plan.rung == 1 and plan.held == ((3, None), (2, None), (1, 500))
    assert "at rung 3 more than the compiler can place" in str(plan)


def test_a_compile_that_fails_otherwise_or_at_rung_0_is_raised():
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        _fit((None, None, None, None), 10**15)
    with pytest.raises(jax.errors.JaxRuntimeError, match="INTERNAL"):
        _fit((1, 1, 1, jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed")), 10**15)


def test_a_step_no_model_reads_the_rung_of_has_nothing_to_fit():
    program, plan, built = _fit(HELD, 16, reads=False)
    assert plan is None and built == [program]  # one compile, whatever it counts


def test_no_limit_is_the_trainers_own_program_with_nothing_applied(monkeypatch):
    monkeypatch.setattr(remat, "device_limit_bytes", lambda devices: None)
    fn = lambda x: x
    applied = []
    step = remat.fitted(lambda trace: applied.append(trace(fn)) or jax.jit(fn), ())
    assert applied == [fn] and step.remat_plan is None and step(3) == 3


def test_a_fitted_step_fits_once_says_so_once_and_lowers_what_it_runs(
    monkeypatch, capsys
):
    monkeypatch.setattr(remat, "device_limit_bytes", lambda devices: 10**15)
    built = []

    def build(trace):
        built.append(jax.jit(trace(lambda x: x * remat.rung())))
        return built[-1]

    step = remat.fitted(build, ())
    assert step.remat_plan is None and not built  # until the first call
    assert [int(step(jnp.int32(2))) for _ in range(3)] == [2 * TOP] * 3
    assert len(built) == 1 and step.remat_plan.rung == TOP
    assert "mul" in step.lower(jnp.int32(2)).as_text() and len(built) == 1
    err = capsys.readouterr().err
    assert err.count("remat: rung") == 1 and str(step.remat_plan) in err


# -- the names ---------------------------------------------------------------------


def _eqns(jaxpr):
    """Every equation of a jaxpr, through its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns") and eqn.primitive.name != "pallas_call":
                    yield from _eqns(inner)


def _names(jaxpr):
    """The names its `name` equations bind."""
    return [e.params["name"] for e in _eqns(jaxpr) if e.primitive.name == "name"]


def test_a_name_lowers_to_nothing():
    x = jnp.ones(3)
    named = jax.jit(lambda v: checkpoint_name(v * 2, remat.MLP_UP) + 1)
    plain = jax.jit(lambda v: v * 2 + 1)
    assert named.lower(x).as_text() == plain.lower(x).as_text()


@pytest.mark.parametrize("remat_on", [False, True])
@pytest.mark.parametrize("kind", sorted(MODELS))
def test_a_model_names_what_the_ladder_keeps_in_every_trace(kind, remat_on):
    """One set of names whatever the rung and with no remat at all: a
    name is nothing outside a `jax.checkpoint` that saves it, so there is
    one flash forward rule and one model, not a second for keeping."""
    import warnings

    x = jnp.zeros((2, 32), jnp.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # a window layer is dense
        model = TransformerLM(MODELS[kind](remat=remat_on))
        variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)
        grad = jax.grad(lambda p: model.apply(p, x).sum())
        names = _names(jax.make_jaxpr(grad)(variables).jaxpr)
    assert set(names) == set(remat.LADDER[-1])


# -- what the lowered step recomputes --------------------------------------------


def _paths(lowered):
    return {p for p in re.findall(r'loc\("([^" ]+)"', lowered.as_text(debug_info=True))
            if "/" in p}


def _kernel_calls(jaxpr):
    """How often each Pallas kernel is called, by the `name` its call gives it."""
    out = {}
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["jaxpr"].debug_info.func_name
            out[name] = out.get(name, 0) + 1
    return out


@pytest.mark.parametrize("trainer", ["ddp_world1", "fsdp"])
def test_the_top_rung_runs_the_kernel_and_the_mlp_products_once_a_layer(
    trainer, world, monkeypatch
):
    """Rung 0's backward runs each block's forward again: a second flash
    forward call and a second gate and up product a layer. At the top rung
    the rematerialised part holds neither."""
    found = {}
    for rung in (0, TOP):
        force(monkeypatch, rung)
        cfg, step, params, opt_state, x, lower = build("dense_gqa", trainer, True, world)
        lowered = lower()  # fits
        program = step if trainer == "fsdp" else step._jitted
        args = (params, opt_state, x, x) if trainer == "fsdp" else (
            params, opt_state, {}, x, x, jax.random.PRNGKey(0))
        assert program.remat_plan.rung == rung
        jaxpr = jax.make_jaxpr(program._program)(*args)
        recomputed = {p for p in _paths(lowered) if "rematted_computation" in p}
        found[rung] = (
            _kernel_calls(jaxpr.jaxpr),
            {(layer, proj) for p in recomputed for layer, proj in re.findall(
                r"layers_(\d)/mlp/(gate|up)_proj/dot_general", p)},
            {layer for p in recomputed for layer in re.findall(
                r"layers_(\d)/attn/[qkv]_proj/dot_general", p)},
        )
    layers = cfg.n_layers
    kernels0, products0, projections0 = found[0]
    kernels3, products3, projections3 = found[TOP]
    # forward, recomputed forward and the one backward a layer; then one forward
    assert sum(kernels0.values()) == 3 * layers
    assert sum(kernels3.values()) == 2 * layers
    assert (kernels0["flash_fwd"], kernels3["flash_fwd"]) == (2 * layers, layers)
    assert kernels0["flash_bwd"] == kernels3["flash_bwd"] == layers
    assert products0 == {(str(i), p) for i in range(layers) for p in ("gate", "up")}
    assert projections0 == {str(i) for i in range(layers)}
    assert products3 == set() and projections3 == set()


@pytest.mark.parametrize("how", ["no limit reported", "nothing richer fits"])
@pytest.mark.parametrize("trainer", ["ddp_world1", "fsdp"])
def test_rung_0_lowers_as_plain_nn_remat_does(trainer, how, world, monkeypatch):
    """No limit reported (the CPU, as it is), or a limit that only rung 0
    is taken under: the step's StableHLO is, text for text, that of
    `nn.remat(Block)` with no policy at both call sites."""
    if how == "nothing richer fits":
        monkeypatch.setattr(remat, "device_limit_bytes", lambda devices: 16)
    texts = []
    for plain in (False, True):
        if plain:
            monkeypatch.setattr(tfm, "_remat_block", lambda: nn.remat(tfm.Block))
            monkeypatch.setattr(remat, "device_limit_bytes", lambda devices: None)
        for kind in sorted(MODELS):
            _, step, *_, lower = build(kind, trainer, True, world)
            texts.append(lower().as_text())
            plan = (step if trainer == "fsdp" else step._jitted).remat_plan
            if plain or how == "no limit reported":
                assert plan is None
            else:
                assert plan.rung == 0 and [r for r, _ in plan.held] == [3, 2, 1, 0]
    assert texts[:2] == texts[2:]
    assert "stablehlo" in texts[0]
