"""Flash attention kernel tests (interpret mode on the CPU test mesh).

Forward and backward vs dense softmax attention; causal + non-causal;
integration with Ulysses context parallelism.
"""

import numpy as np
import pytest

from pytorch_distributed_example_tpu.ops import flash_attention


def _dense(q, k, v, causal, scale=None):
    import jax
    import jax.numpy as jnp

    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        L, Lk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(L)[:, None] >= jnp.arange(Lk)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _rand_qkv(seed, B=2, L=256, H=2, D=32, dtype="float32"):
    import jax.numpy as jnp

    gen = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(gen.standard_normal((B, L, H, D)), dtype)
    return mk(), mk(), mk()


def _f32(x):
    return np.asarray(x, dtype=np.float32)


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = _rand_qkv(0)
        got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        want = _dense(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_uneven_blocks(self):
        # block_q != block_k exercises the diagonal-block bounds
        q, k, v = _rand_qkv(1, L=256)
        got = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
        want = _dense(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
    def test_mask_is_exact_where_the_diagonal_crosses(self, bq, bk):
        """Only the block pairs the diagonal crosses take the mask, and the
        split must not move a value: row 0 attends itself alone, so its
        output IS v[0]; a block pair wholly over the diagonal is never
        visited, so NaNs in the last key block reach no earlier row (a
        visited pair would add 0 * NaN)."""
        import jax.numpy as jnp

        q, k, v = _rand_qkv(21, B=1, L=256)
        poison = jnp.arange(256)[None, :, None, None] >= 256 - bk
        k, v = jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v)
        got = np.asarray(flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk))
        np.testing.assert_array_equal(got[:, 0], np.asarray(v)[:, 0])
        clean = 256 - max(bq, bk)  # the q blocks that end before the NaNs
        want = _dense(q[:, :clean], k[:, :clean], v[:, :clean], True)
        np.testing.assert_allclose(got[:, :clean], np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_small_seq_clamps_blocks(self):
        q, k, v = _rand_qkv(2, L=32)
        got = flash_attention(q, k, v, causal=False)
        want = _dense(q, k, v, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_bad_seq_len_raises(self):
        import jax.numpy as jnp

        q = jnp.zeros((1, 96, 1, 16))
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(q, q, q, block_q=64, block_k=64)


def _tol(dtype):
    return dict(rtol=5e-4, atol=5e-4) if dtype == "float32" else dict(rtol=5e-2, atol=5e-2)


class TestFlashBackward:
    """The resident regime's ONE backward kernel (scores, p and dlogits of a
    block pair computed once, dQ in a VMEM accumulator across key blocks)
    against the dense reference and against the two streamed kernels."""

    # L = 4 x 64: pairs wholly under, on and over the diagonal all occur
    @pytest.mark.parametrize("dtype,L", [("float32", 128), ("float32", 256), ("bfloat16", 256)])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("bq,bk", [(64, 64), (64, 32), (32, 64)])
    def test_grads_match_dense(self, causal, bq, bk, dtype, L):
        import jax

        q, k, v = _rand_qkv(3, B=1, L=L, H=2, D=16, dtype=dtype)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
            return (o.astype("float32") ** 2).sum()

        def loss_dense(q, k, v):
            o = _dense(*(x.astype("float32") for x in (q, k, v)), causal)
            return (o * o).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            assert a.dtype == q.dtype
            np.testing.assert_allclose(
                _f32(a), _f32(b), **_tol(dtype), err_msg=f"d{name} mismatch",
            )

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128), (96, 64)])
    def test_one_pass_matches_the_two_kernel_form(self, causal, bq, bk, dtype, monkeypatch):
        """Same residuals, same delta (with a dlse term in it), both
        lowerings of `_bwd`: the forms differ in where dQ is summed, not in
        what is summed. (96, 64): neither block size divides the other, so
        every visited pair takes the mask."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.ops.flash_attention import (
            _bwd, _fwd, _to_bh as _bh,
        )

        L = 384 if bq == 96 else 256
        q, k, v = (_bh(x) for x in _rand_qkv(17, B=1, L=L, H=2, D=32, dtype=dtype))
        do, dlse, _ = (_bh(x) for x in _rand_qkv(18, B=1, L=L, H=2, D=32, dtype=dtype))
        dlse = dlse[..., :1].astype(jnp.float32)
        scale = 32 ** -0.5
        o, lse = _fwd(q, k, v, scale, causal, bq, bk, True)
        forms = {}
        for stream in ("0", "1"):
            monkeypatch.setenv("TDX_FLASH_STREAM", stream)
            forms[stream] = _bwd(q, k, v, o, lse, do, scale, causal, bq, bk, True, dlse=dlse)
        tol = dict(rtol=2e-5, atol=2e-5) if dtype == "float32" else _tol(dtype)
        for one, two, name in zip(forms["0"], forms["1"], "qkv"):
            assert one.dtype == two.dtype == q.dtype
            np.testing.assert_allclose(_f32(one), _f32(two), **tol, err_msg=f"d{name}")

    @pytest.mark.parametrize("poisoned", ["last_keys", "first_queries"])
    def test_no_gradient_crosses_a_pair_over_the_diagonal(self, poisoned):
        """The backward's loop starts at the first q block that sees the key
        block. NaNs in the last key block reach dQ of no earlier row, NaNs
        in the first q block reach dK, dV of no later key block (a visited
        pair would add 0 * NaN or exp(NaN)); row 0 attends itself alone, so
        its dQ is 0."""
        import jax
        import jax.numpy as jnp

        q, k, v = _rand_qkv(23, B=1, L=256, H=1, D=16)
        pos = jnp.arange(256)[None, :, None, None]
        if poisoned == "last_keys":
            k, v = jnp.where(pos >= 192, jnp.nan, k), jnp.where(pos >= 192, jnp.nan, v)
        else:
            q = jnp.where(pos < 64, jnp.nan, q)

        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
            return (o ** 2).sum()

        dq, dk, dv = (np.asarray(g) for g in jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
        if poisoned == "last_keys":
            assert np.isfinite(dq[:, :192]).all() and not np.isfinite(dq[:, 192:]).any()
            np.testing.assert_array_equal(dq[:, 0], 0.0)
        else:
            assert np.isfinite(dq[:, 64:]).all()
            assert np.isfinite(dk[:, 64:]).all() and np.isfinite(dv[:, 64:]).all()
            assert not np.isfinite(dk[:, :64]).any()


class TestFwdOutDtype:
    def test_f32_partials_for_ring_combine(self):
        """ADVICE r5 #2: `_fwd(..., out_dtype=f32)` hands the ring
        combine the kernel's f32 accumulator directly. Contract: the
        default output is still q.dtype, and the f32 output rounds to
        EXACTLY the default bf16 output (same accumulator, one cast)."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.ops.flash_attention import (
            _fwd,
            _interpret_default,
        )

        gen = np.random.default_rng(5)
        BH, L, D = 4, 256, 32
        mk = lambda: jnp.asarray(
            gen.standard_normal((BH, L, D)), jnp.bfloat16
        )
        q, k, v = mk(), mk(), mk()
        interp = _interpret_default()
        o16, lse16 = _fwd(q, k, v, 1.0 / D ** 0.5, True, 64, 64, interp)
        o32, lse32 = _fwd(
            q, k, v, 1.0 / D ** 0.5, True, 64, 64, interp,
            out_dtype=jnp.float32,
        )
        assert o16.dtype == jnp.bfloat16 and o32.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(o32.astype(jnp.bfloat16), dtype=np.float32),
            np.asarray(o16, dtype=np.float32),
        )
        np.testing.assert_array_equal(np.asarray(lse32), np.asarray(lse16))
        # and the f32 output genuinely carries sub-bf16 precision
        assert not np.array_equal(
            np.asarray(o32),
            np.asarray(o32.astype(jnp.bfloat16).astype(jnp.float32)),
        )


class TestFlashStreamed:
    """The long-context streamed variant: k/v blocks ride the grid with
    scratch accumulators instead of sitting whole in VMEM (unlocks
    single-chip L=64k, measured on hardware — `flash_sweep_L65536_*`
    rows). Forced on here via env; selected automatically past
    L·D ≈ 1.5M elements. Measured bitwise-identical to the resident
    kernels on TPU; pinned here against the dense oracle in interpret
    mode."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_streamed_matches_dense_fwd_bwd(self, causal, monkeypatch):
        import jax

        monkeypatch.setenv("TDX_FLASH_STREAM", "1")
        q, k, v = _rand_qkv(11, B=1, L=256, H=2, D=64)

        o = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
        ref = _dense(q, k, v, causal)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(ref), rtol=2e-4, atol=2e-4
        )

        def loss_flash(q, k, v):
            o = flash_attention(
                q, k, v, causal=causal, block_q=128, block_k=128
            )
            return (o * o).sum()

        def loss_dense(q, k, v):
            return (_dense(q, k, v, causal) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4,
                err_msg=f"d{name} mismatch (streamed)",
            )

    @pytest.mark.parametrize("bq,bk", [(128, 128), (64, 128), (128, 64)])
    @pytest.mark.parametrize("stream", [False, True])
    def test_flash_with_lse_dlse_gradient(self, stream, bq, bk, monkeypatch):
        """`flash_with_lse`'s VJP propagates the LSE cotangent (folded
        into the bwd kernels as `delta - dlse`) — pinned directly, both
        lowerings, against a dense (o, logsumexp) reference whose loss
        consumes BOTH outputs."""
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.ops.flash_attention import (
            flash_with_lse,
        )

        monkeypatch.setenv("TDX_FLASH_STREAM", "1" if stream else "0")
        q, k, v = _rand_qkv(13, B=1, L=256, H=2, D=64)
        scale = 1.0 / (64 ** 0.5)

        def loss_flash(q, k, v):
            o, lse = flash_with_lse(q.transpose(0, 2, 1, 3).reshape(2, 256, 64),
                                    k.transpose(0, 2, 1, 3).reshape(2, 256, 64),
                                    v.transpose(0, 2, 1, 3).reshape(2, 256, 64),
                                    scale, True, bq, bk, True)
            return (o.astype(jnp.float32) ** 2).sum() + (lse ** 2).sum()

        def loss_dense(q, k, v):
            qb = q.transpose(0, 2, 1, 3).reshape(2, 256, 64)
            kb = k.transpose(0, 2, 1, 3).reshape(2, 256, 64)
            vb = v.transpose(0, 2, 1, 3).reshape(2, 256, 64)
            s = jnp.einsum("bqd,bkd->bqk", qb, kb) * scale
            mask = jnp.arange(256)[:, None] >= jnp.arange(256)[None, :]
            s = jnp.where(mask[None], s, -1e30)
            lse = jax.nn.logsumexp(s, axis=-1)[..., None]
            o = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), vb)
            return (o ** 2).sum() + (lse ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3,
                err_msg=f"d{name} mismatch (dlse path, stream={stream})",
            )

    def test_auto_selection_threshold(self, monkeypatch):
        from pytorch_distributed_example_tpu.ops.flash_attention import (
            _use_streaming,
        )

        monkeypatch.delenv("TDX_FLASH_STREAM", raising=False)
        monkeypatch.delenv("TDX_FLASH_VMEM_MB", raising=False)
        assert not _use_streaming(2048, 128)       # resident: fastest, fits
        assert _use_streaming(16384, 128)          # the measured OOM point
        assert _use_streaming(8192, 128, itemsize=4)  # fp32 halves budget
        monkeypatch.setenv("TDX_FLASH_STREAM", "0")
        assert not _use_streaming(65536, 128)      # explicit override wins

    def test_stream_env_strict_parse(self, monkeypatch):
        """ADVICE r5 #3: '1'/'0' force, unset/'' auto, junk raises (a
        typo like 'true' used to silently force the VMEM-resident
        kernels back on at OOM lengths)."""
        from pytorch_distributed_example_tpu.ops.flash_attention import (
            _use_streaming,
        )

        monkeypatch.setenv("TDX_FLASH_STREAM", "")
        assert _use_streaming(16384, 128)  # '' = auto, not force-off
        monkeypatch.setenv("TDX_FLASH_STREAM", "1")
        assert _use_streaming(128, 16)
        for junk in ("true", "yes", "2", "on"):
            monkeypatch.setenv("TDX_FLASH_STREAM", junk)
            with pytest.raises(ValueError, match="TDX_FLASH_STREAM"):
                _use_streaming(16384, 128)

    def test_env_block_fit_warns_once(self, monkeypatch):
        """ADVICE r5 #5: a fleet-wide TDX_FLASH_BLOCK_Q/K that fit()
        must alter warns (once per distinct alteration) so env
        misconfigurations stay auditable; per-call overrides never
        warn."""
        import importlib
        import warnings as _warnings

        fa = importlib.import_module(
            "pytorch_distributed_example_tpu.ops.flash_attention"
        )

        monkeypatch.setenv("TDX_FLASH_BLOCK_Q", "768")  # cannot tile 1024
        monkeypatch.delenv("TDX_FLASH_BLOCK_K", raising=False)
        fa._env_fit_warned.clear()
        with _warnings.catch_warnings(record=True) as w:
            _warnings.simplefilter("always")
            bq, _ = fa.resolved_block_sizes(1024)
            fa.resolved_block_sizes(1024)  # same alteration: no 2nd warning
        assert bq == 128
        hits = [x for x in w if "TDX_FLASH_BLOCK_Q" in str(x.message)]
        assert len(hits) == 1
        # a tiling env block stays silent
        monkeypatch.setenv("TDX_FLASH_BLOCK_Q", "256")
        with _warnings.catch_warnings(record=True) as w2:
            _warnings.simplefilter("always")
            bq2, _ = fa.resolved_block_sizes(1024)
        assert bq2 == 256
        assert not [x for x in w2 if "TDX_FLASH_BLOCK" in str(x.message)]


class TestFlashWithUlysses:
    def test_flash_as_ulysses_kernel(self):
        """flash_attention slots in as the Ulysses local attention kernel."""
        from pytorch_distributed_example_tpu.mesh import init_device_mesh
        from pytorch_distributed_example_tpu.parallel import make_cp_attention

        mesh = init_device_mesh(("sp",), (8,))
        q, k, v = _rand_qkv(4, B=1, L=256, H=8, D=16)

        attn = make_cp_attention(
            mesh, axis_name="sp", mode="ulysses", causal=True, attn_fn=flash_attention
        )
        got = attn(q, k, v)
        want = _dense(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
