"""Valid-padding NHWC conv with a CPU-tuned backward schedule.

XLA:CPU lowers the INPUT-gradient of a convolution to a transposed
direct conv that measures ~2x slower than routing the same cotangent
through an im2col formulation (one CPU host, 12x12x10 -> 8x8x20 k5 grads:
5.6 ms lax vs 2.7 ms im2col; the forward and weight-grad direct convs
are already the fast path). `conv2d_valid_nhwc` is therefore a
custom_vjp whose backward mixes the best lowering per operand:

  forward:     lax.conv_general_dilated       (direct conv, fast)
  dW:          vjp of the direct conv          (direct conv, fast)
  dX:          vjp of the im2col formulation   (matmul + 25 slice-adds)

The im2col graph computes the IDENTICAL convolution (asserted in
tests/test_models.py), so gradients match the lax path to float
rounding; only the schedule differs. On TPU the MXU's native conv
transpose is the fast path, so the custom schedule is gated to the CPU
backend at trace time and every other platform gets the plain lax conv
(with XLA's own transpose rules).

Use this op only where the input gradient is actually needed: a
custom_vjp always computes every cotangent, so a first-layer conv
(whose input is data, never differentiated) would pay for a dX the
plain path skips — keep nn.Conv there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_DNUMS = ("NHWC", "HWIO", "NHWC")


def _conv_direct(x, w):
    return lax.conv_general_dilated(x, w, (1, 1), "VALID",
                                    dimension_numbers=_DNUMS)


def _conv_im2col(x, w):
    """Same conv as matmul over K*K shifted slices (static K)."""
    K = w.shape[0]
    b, h, wd, cin = x.shape
    cout = w.shape[-1]
    ho, wo = h - K + 1, wd - K + 1
    cols = [
        lax.slice(x, (0, i, j, 0), (b, i + ho, j + wo, cin))
        for i in range(K)
        for j in range(K)
    ]
    patches = jnp.concatenate(cols, axis=-1)  # (b, ho, wo, K*K*cin)
    wm = w.reshape(K * K * cin, cout)
    return (patches.reshape(-1, K * K * cin) @ wm).reshape(b, ho, wo, cout)


@jax.custom_vjp
def _conv2d_cpu(x, w):
    return _conv_direct(x, w)


def _cpu_fwd(x, w):
    return _conv_direct(x, w), (x, w)


def _cpu_bwd(res, ct):
    x, w = res
    _, vjp_w = jax.vjp(lambda ww: _conv_direct(x, ww), w)
    _, vjp_x = jax.vjp(lambda xx: _conv_im2col(xx, w), x)
    return vjp_x(ct)[0], vjp_w(ct)[0]


_conv2d_cpu.defvjp(_cpu_fwd, _cpu_bwd)


def conv2d_valid_nhwc(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """NHWC VALID conv, square kernel w: (K, K, Cin, Cout), stride 1.

    Dispatches to the CPU-tuned custom_vjp on the CPU backend (a
    trace-time decision: the model rebuilds per backend under jit) and
    to the plain lax conv everywhere else.
    """
    if jax.default_backend() == "cpu":
        return _conv2d_cpu(x, w)
    return _conv_direct(x, w)
