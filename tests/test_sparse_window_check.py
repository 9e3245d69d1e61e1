"""The patterned model's two pieces that exist for the chip, at a small size
on the CPU: the comparison the benchmark's cell makes (`correct`), whose
float32 reference is told the system's routing through the glue's replay of
the prefill, and the one Pallas kernel (`ops/grouped_mlp.py`) the sparse
layers' three grouped products are where their shapes tile. A file beside `test_sparse_window.py` (whose toy
configuration, weights and helpers it shares) so that the two run on two
workers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_matrix import correctness, modelglue
from bench_matrix.glue import sparse_window as glue
from pytorch_distributed_example_tpu.serve import ServeEngine

from test_sparse_window import (  # noqa: F401  (`small` is a fixture)
    ASKING, BS, LIMITS, M, SMALL, TOP_K, Probe, reference_logits, small, tokens_of,
)

# --- the check on the chip: the reference told the system's choices ----------

def test_the_replay_tells_the_experts_the_model_chose(small):
    """`Layers.system_routing` prefills whole chunks through a paged cache
    of its own and fetches what the sparse layers sowed: in float32 the
    experts of the cache-free forward, and -1 for what is left over."""
    model, variables = small
    tokens = tokens_of(45, 5)
    _, inter = model.apply(variables, jnp.asarray(tokens)[None], mutable=["intermediates"])
    layers = glue.reference_parts(variables)[1]
    told = layers.system_routing(tokens, dict(SMALL, model=ASKING))
    assert sorted(told) == [1, 2, 3, 4]
    for i, got in told.items():
        want = np.asarray(inter["intermediates"][f"layers_{i}"]["mlp"]["moe_chosen"][0][0])
        assert got.shape == (45, TOP_K) and got.dtype == np.int32
        np.testing.assert_array_equal(np.sort(got[:40], 1), np.sort(want[:40], 1))
        assert (got[40:] == -1).all()


def test_a_told_choice_is_taken_only_inside_the_tie_margin(small):
    """Token 3 of layer 2 is told the reference's own first expert and its
    THIRD (the first it did not choose): taken where the margin allows it
    (another function), refused where it does not (the reference's own
    logits, bit for bit), and a token told nothing routes itself."""
    model, variables = small
    tokens = tokens_of(24, 11)
    record = []
    own = reference_logits(variables, tokens, 24, record=record)
    chosen = np.asarray(record[1]["chosen"])
    margin = float(record[1]["margin"][3])
    w = next(l for i, l in enumerate(glue.reference_parts(variables)[1]) if i == 2)
    assert record[1]["layer"] == 2 and margin > 0 and "router" in w
    told = np.full((24, TOP_K), -1, np.int32)
    told[5] = chosen[5][::-1]  # its own two, the other way round
    for third in [e for e in range(8) if e not in chosen[3]]:
        told[3] = [chosen[3][0], third]
        wide, narrow = [], []
        a = reference_logits(variables, tokens, 24, routing={2: told}, tie_margin=1.0, record=wide)
        b = reference_logits(variables, tokens, 24, routing={2: told}, tie_margin=margin / 2,
                             record=narrow)
        assert int(wide[1]["differs"].sum()) == 1 and not wide[1]["refused"].any()
        assert not correctness.compare(a, own, LIMITS)["ok"]
        assert int(narrow[1]["refused"].sum()) == 1 and bool(narrow[1]["refused"][3])
        np.testing.assert_array_equal(b, own)
    told[3] = [chosen[3][0], chosen[3][0]]  # one expert twice is no choice
    again = []
    c = reference_logits(variables, tokens, 24, routing={2: told}, tie_margin=1.0, record=again)
    assert bool(again[1]["refused"][3])
    np.testing.assert_array_equal(c, own)


TOLD_LIMITS = {"max_rel": 0.1, "rms_rel": 0.06}


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_told_the_system_s_choices_a_bfloat16_engine_meets_limits_float8_does_not(seed, capfd):
    """The comparison the benchmark's cell makes, at the small size in
    bfloat16: the last chunks' logits of a chunked prefill against the
    float32 reference. Routing for itself the reference reads the flipped
    choices (rms 0.09-0.23); told the system's it agrees to 0.03, and its
    expert products or its K/V in float8 then fail the same limits."""
    dtype = {k: "bfloat16" for k in ("weights", "activations", "kv_cache")}
    plain, asking = dict(SMALL, dtype=dtype), dict(SMALL, dtype=dtype, model=ASKING)
    model = modelglue.build_model(plain, M, remat=False)
    variables = modelglue.make_variables(model, plain, seed)
    engine = ServeEngine(model, variables, slots=2, block_size=BS, pool_blocks=96,
                         prefill_chunk_tokens=8, min_bucket=4)
    probe = engine._prefill_chunk = Probe(engine._prefill_chunk)
    prompt = tokens_of(48, seed)
    engine.submit(prompt, 2, rid="check")
    while engine.step():
        pass
    got = np.concatenate([lg[:len(t)] for _, t, lg in probe.chunks])
    assert got.shape[0] == 48
    assert not correctness.compare(
        got, reference_logits(variables, prompt, 48, plain), TOLD_LIMITS)["ok"]
    capfd.readouterr()
    told = correctness.compare(got, reference_logits(variables, prompt, 48, asking), TOLD_LIMITS)
    assert told["ok"], told
    said = capfd.readouterr().err
    assert "of 192 (token, sparse layer) choices told by the system" in said
    assert "0 were refused" in said
    for kw in ({"expert_dtype": jnp.float8_e4m3fn}, {"kv_dtype": jnp.float8_e4m3fn}):
        low = reference_logits(variables, prompt, 48, asking, **kw)
        assert not correctness.compare(got, low, TOLD_LIMITS)["ok"], kw


# --- the grouped kernel -------------------------------------------------------

@pytest.mark.parametrize("rows,d_in,d_mid,dtype,ok", [
    (256, 2048, 512, jnp.bfloat16, True),    # a decode step of the cell: 32 rows x 8
    (4096, 2048, 512, jnp.bfloat16, True),   # its 512-token chunk
    (48, 64, 32, jnp.float32, False),        # the toy of these tests
    (200, 2048, 512, jnp.bfloat16, False),   # a ragged last row tile
    (256, 2048, 384, jnp.bfloat16, False),   # an expert width that is no whole tile
    (256, 2048, 512, jnp.float32, False),    # operands the tiling was not measured with
])
def test_which_sparse_layers_take_the_grouped_kernel(rows, d_in, d_mid, dtype, ok):
    from pytorch_distributed_example_tpu.parallel.expert_parallel import grouped_kernel_ok

    assert grouped_kernel_ok(rows, d_in, d_mid, dtype) is ok


@pytest.mark.parametrize("held", [(0, 8), (2, 4)], ids=["all", "a_range"])
def test_the_grouped_kernel_is_the_grouped_product(monkeypatch, held):
    """At sizes that tile (bfloat16, 64 rows x 2 = one row tile, experts of
    512 x 512) `dropless_moe` runs the one Pallas kernel, interpreted here: the
    layer it gives is the `ragged_dot` one to bfloat16's rounding, with
    masked rows, experts no row chose and a held range; the counters are
    equal."""
    from pytorch_distributed_example_tpu.parallel import expert_parallel as ep

    T, D, F, E, K = 64, 512, 512, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (T, D)).astype(jnp.bfloat16)
    router = jax.random.normal(ks[1], (D, E)) * D ** -0.5
    wg, wu = (jax.random.normal(k, (E, D, F)) * D ** -0.5 for k in ks[2:4])
    wd = jax.random.normal(ks[4], (E, F, D)) * F ** -0.5
    first, count = held
    wg, wu, wd = (w[first:first + count].astype(jnp.bfloat16) for w in (wg, wu, wd))
    mask = jnp.arange(T) % 3 != 0
    assert ep.grouped_kernel_ok(T * K, D, F, x.dtype)
    run = lambda: jax.jit(lambda *a: ep.dropless_moe(
        *a, n_experts=E, top_k=K, scale=2.5, first_expert=first, row_mask=mask))(
            x, router, wg, wu, wd)
    y, stats, chosen = run()
    monkeypatch.setattr(ep, "grouped_kernel_ok", lambda *a: False)
    y0, stats0, chosen0 = run()
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(stats0))
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen0))
    y, y0 = np.asarray(y, np.float32), np.asarray(y0, np.float32)
    assert np.isfinite(y).all() and not y[~np.asarray(mask)].any()
    np.testing.assert_allclose(y, y0, atol=2e-2 * np.abs(y0).max())


def _experts(D, F, G, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    wg, wu = (jax.random.normal(k, (G, D, F)) * D ** -0.5 for k in ks[:2])
    wd = jax.random.normal(ks[2], (G, F, D)) * F ** -0.5
    return tuple(w.astype(jnp.bfloat16) for w in (wg, wu, wd))


def _kernel_against_ragged(D, F, sizes, rows, tf):
    """The kernel at tiles of `tf` and the `ragged_dot` form over the
    same sorted rows: (got, want), the rows that belong to a group."""
    from pytorch_distributed_example_tpu.ops import grouped_mlp as gm

    sizes = jnp.asarray(sizes, jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(3), (rows, D)).astype(jnp.bfloat16)
    w = _experts(D, F, sizes.shape[0])
    got = gm.grouped_swiglu_kernel(x, *w, sizes, tf, True)
    placed = int(sizes.sum())
    return got[:placed], gm.ragged_swiglu(x, *w, sizes)[:placed]


def _layer_against_ragged(monkeypatch, T, K, E, held, bias, D=256, F=256):
    """`dropless_moe` holding experts `held` of `E`, once through the kernel
    and once with the predicate saying no: (got, want, few, total)."""
    from pytorch_distributed_example_tpu.ops.grouped_mlp import ROW_TILE
    from pytorch_distributed_example_tpu.parallel import expert_parallel as ep

    first, count = held
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    x = jax.random.normal(ks[0], (T, D)).astype(jnp.bfloat16)
    router = jax.random.normal(ks[1], (D, E)) * D ** -0.5
    w = tuple(a[first:first + count] for a in _experts(D, F, E))
    few = -(-4 * T * K * count // (E * ROW_TILE)) * ROW_TILE
    assert few < T * K and ep.grouped_kernel_ok(few, D, F, x.dtype)
    mask = jnp.arange(T) % 5 != 0
    run = lambda: jax.jit(lambda *a: ep.dropless_moe(
        *a, n_experts=E, top_k=K, first_expert=first, row_mask=mask, score="sigmoid",
        choice_bias=jnp.asarray(bias, jnp.float32)))(x, router, *w)
    y, stats, _ = run()
    monkeypatch.setattr(ep, "grouped_kernel_ok", lambda *a: False)
    y0, stats0, _ = run()
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(stats0))
    assert not np.asarray(y, np.float32)[~np.asarray(mask)].any()
    return y, y0, few, int(stats[0])


@pytest.mark.parametrize("case", [
    "an_expert_no_row_chose", "a_group_over_two_row_tiles", "rows_past_the_last_group",
    "the_expert_width_in_three_tiles", "a_width_512_does_not_divide",
    "a_held_range_its_leading_rows", "a_held_range_every_row",
])
def test_the_one_kernel_is_the_ragged_dot_form(monkeypatch, case):
    """`ops/grouped_mlp.py`'s kernel, interpreted here, against three
    `ragged_dot`s to bfloat16's rounding: the work list's corners (an empty
    group, a group that reaches into a second row tile and shares both with
    others, row tiles no group reaches), the expert width in more than one
    tile (the output tile accumulates over them), a hidden size that is whole
    lane tiles and not whole 512s (3584 scaled down: 7 x 128), and a caller
    that holds experts 2-3 of 16 through both branches of
    `_share_of_assignments` (the bias sends every row's choices away from
    the held range but a few, or all of them into it)."""
    if case.startswith("a_held_range"):
        into = case.endswith("every_row")
        bias = [(4.0 if into else -4.0) if e in (2, 3) else 0.0 for e in range(16)]
        bias[2] = 4.0 if into else 0.0  # some rows still choose a held expert
        got, want, few, total = _layer_against_ragged(
            monkeypatch, T=128, K=2, E=16, held=(2, 2), bias=bias)
        assert (total > few) is into and total > 0, (total, few)
    else:
        D, F, sizes, rows, tf = {
            "an_expert_no_row_chose": (256, 512, [40, 0, 60, 0, 0, 28], 128, 512),
            "a_group_over_two_row_tiles": (256, 512, [100, 60, 96], 256, 512),
            "rows_past_the_last_group": (256, 512, [30, 50], 384, 512),
            "the_expert_width_in_three_tiles": (256, 768, [100, 0, 60, 96], 256, 256),
            "a_width_512_does_not_divide": (896, 256, [70, 58, 1, 127], 256, 256),
        }[case]
        got, want = _kernel_against_ragged(D, F, sizes, rows, tf)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all() and np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-2 * np.abs(want).max())


def test_the_sparse_layer_s_gradient_is_the_ragged_dot_form_s(monkeypatch):
    """`jax.grad` through `dropless_moe` at a shape the kernel takes: its
    backward is the VJP of the `ragged_dot` form, so the gradients of a
    loss linear in the output are that form's, to the forward's rounding
    where the loss is not."""
    from pytorch_distributed_example_tpu.parallel import expert_parallel as ep

    T, D, F, E, K = 64, 256, 512, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (T, D)).astype(jnp.bfloat16)
    router = jax.random.normal(ks[1], (D, E)) * D ** -0.5
    w = _experts(D, F, E)
    towards = jax.random.normal(ks[2], (T, D))
    assert ep.grouped_kernel_ok(T * K, D, F, x.dtype)

    def loss(x, router, wg, wu, wd):
        y, _, _ = ep.dropless_moe(x, router, wg, wu, wd, n_experts=E, top_k=K)
        y = y.astype(jnp.float32)
        return jnp.sum(y * towards) + 0.5 * jnp.sum(y * y)

    grads = lambda: jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(x, router, *w)
    got = grads()
    monkeypatch.setattr(ep, "grouped_kernel_ok", lambda *a: False)
    want = grads()
    for g, g0 in zip(got, want):
        g, g0 = np.asarray(g, np.float32), np.asarray(g0, np.float32)
        assert g.shape == g0.shape and np.abs(g0).max() > 0
        np.testing.assert_allclose(g, g0, atol=2e-2 * np.abs(g0).max())
