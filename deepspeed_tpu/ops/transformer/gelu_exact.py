"""The exact (erf) GELU as one unit with its own backward.

``gelu_exact(h)`` is ``a = h * Phi(h)``, ``Phi`` the standard normal's
distribution function (``0.5 * erfc(-h / sqrt(2))``): the activation of the
GPT-NeoX / Pythia, BERT, Falcon and MPT families (HF ``hidden_act: "gelu"``;
reference ``csrc/transformer/gelu_kernels.cu``). Under its ``jax.custom_vjp``
the forward rule returns ``a`` and the residual ``g = Phi(h) + h * phi(h)``,
the derivative, from ONE evaluation an element, and the backward rule is
``d * g``. Both are computed in float32 from ``h`` and rounded once to its
dtype, and both stand behind an ``optimization_barrier``: the compiler may
attach the evaluation to the product that makes ``h`` (its epilogue, where
every element is produced once) or run it as one loop, but it cannot copy it
into the operands of the three products that read ``a`` and ``g``.

Why a unit (my chip runs, PR 58, TPU v5e; ``PERF.md`` 5): ``jax.nn.gelu(h,
approximate=False)`` reaches the TPU compiler as ``erfc``'s two-branch
expansion, 28 multiplies, 21 adds, 2 divides and an exponential an element
in float32 (v5e has no bfloat16 vector unit), which the compiler put into the
prologue of ``gelu(h) @ w_down``, into ``w_down``'s gradient product and into
the epilogue of ``d_out @ w_down^T``: those three ran at 59-65% of the peak
that their activation-free neighbours reach at 91-95%.

The evaluation. For a 16-bit ``h`` the lower tail is one branch,
``Phi(-t) = exp(-t^2 / 2) * m(t)`` for ``t = |h|``, with ``m`` (Mills' ratio
over ``sqrt(2 pi)``) a degree-8 polynomial in ``1 / (1 + 0.3 t)`` fitted to
``erfcx`` in relative error (5.8e-8 over ``t <= 14``, past which the
exponential is 0 in float32), and ``Phi(t) = 1 - Phi(-t)``. The exponential
is the one ``g`` needs anyway: 15 multiplies, 11 adds, 1 divide and 1
exponential for ``a`` and ``g`` together. Nothing is dropped and nothing
inside is narrower than float32; the tails keep their relative accuracy,
which ``1 - erf`` would lose below ``h = -3``. Over every finite bfloat16
input ``a`` lies within 0.5 and ``g`` within 1.2 roundings of the float64
value, nowhere further from it than ``jax.nn.gelu(approximate=False)`` and
its autodiff, which round at every bfloat16 operation
(``tests/unit/test_gelu_exact.py``). In float32 the fit's own rounding (5
float32 roundings) would show against ``erfc``, so a 32-bit or wider ``h``
keeps the ``erfc`` expression: ``a`` is then ``jax.nn.gelu``'s to the bit.
"""

import math

import jax
import jax.numpy as jnp

SQRT_HALF = math.sqrt(0.5)
INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)
#: ``Phi(-t) / exp(-t^2 / 2)`` as a polynomial in ``1 / (1 + TAIL_P * t)``,
#: constant term first (Lawson-weighted least squares on 4000 Chebyshev nodes)
TAIL_P = 0.3
TAIL_C = (7.168951469793683e-06, 0.1194876879453659, 0.12192035466432571,
          0.09468346834182739, 0.14253567159175873, -0.07650669664144516,
          0.22866028547286987, -0.1691662222146988, 0.038378313183784485)


def _terms(h):
    """``(x, Phi(x), exp(-x^2 / 2))`` for ``x = h`` in float32 or wider."""
    x = h.astype(jnp.promote_types(h.dtype, jnp.float32))
    bell = jnp.exp(-0.5 * x * x)
    if jnp.dtype(h.dtype).itemsize >= 4:
        return x, 0.5 * jax.lax.erfc(-x * SQRT_HALF), bell
    u = 1.0 / (1.0 + TAIL_P * jnp.abs(x))
    m = jnp.full_like(x, TAIL_C[-1])
    for c in TAIL_C[-2::-1]:
        m = m * u + c
    tail = bell * m
    return x, jnp.where(x < 0, tail, 1.0 - tail), bell


@jax.custom_vjp
def gelu_exact(h):
    """``h * Phi(h)`` in ``h``'s dtype, materialised once."""
    x, cdf, _ = _terms(h)
    return jax.lax.optimization_barrier((x * cdf).astype(h.dtype))


def gelu_exact_pair(h):
    """``(a, g)``: the activation and its derivative from one evaluation,
    each rounded once to ``h``'s dtype."""
    x, cdf, bell = _terms(h)
    return ((x * cdf).astype(h.dtype),
            (cdf + x * (bell * INV_SQRT_2PI)).astype(h.dtype))


def _fwd(h):
    return jax.lax.optimization_barrier(gelu_exact_pair(h))


def _bwd(g, d):
    return (d * g,)


gelu_exact.defvjp(_fwd, _bwd)
