"""Plain float32 references, one module per architecture.

Written from the published model descriptions in straightforward ``jax.numpy``:
no kernels, no cache, no batching tricks, and nothing imported from
``deepspeed_tpu``. Every contraction goes through the ``ein`` hook so that the
same text serves as the reference (``ein_f32``: float32 at ``highest`` matmul
precision) and as the lower-precision control (``ein_fp8``, used by
``calibrate.py`` and the tests only, never by a benchmark run).

Weights arrive in the layout the benchmark generates them in (see
``harness/weights.py``): matrices are (in, out), per-layer leaves are stacked on
a leading layer axis under a group such as ``blocks``.

An architecture module gives its forward in parts, so that the serving
reference can walk a model layer by layer with one layer's float32 weights on
the chip at a time (``harness/check.py``):

- ``groups(cfg)``: the stacked groups in forward order, ``[(key, layers), ...]``;
- ``embed(w, ids, cfg)``: (S, H) float32 from the unstacked leaves ``w``;
- ``layer(x, b, cfg, ein)``: one layer over ``b``, that layer's leaves;
- ``final(w, x, cfg)`` and ``logits(w, h, ein)``: the closing norm and the head;
- ``hidden(w, ids, cfg, ein)``: the same parts over a whole tree, layers under
  ``scan_layers``, for the training reference and the tests.
"""

import jax
import jax.numpy as jnp


def ein_f32(spec, a, b):
    """The reference contraction: float32, ``highest`` precision (on a TPU a
    float32 matmul otherwise runs in bfloat16 passes)."""
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _fake_fp8(x):
    """Round to float8 e4m3 under a per-tensor scale (amax -> 448), straight
    through for the gradient."""
    x = x.astype(jnp.float32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def ein_fp8(spec, a, b):
    """The control one step below bfloat16: both operands of every
    contraction rounded to fp8 (e4m3, per-tensor scaling)."""
    return ein_f32(spec, _fake_fp8(a), _fake_fp8(b))


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def causal_attention(q, k, v, ein, q_block=None):
    """q, k, v: (S, heads, head_dim) of one sequence. With ``q_block`` (a
    divisor of S) the queries go ``q_block`` at a time, so that a long
    sequence holds (heads, q_block, S) scores and never (heads, S, S)."""
    s = q.shape[0]

    def rows(q, first):
        scores = ein("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        mask = (first + jnp.arange(q.shape[0]))[:, None] >= jnp.arange(s)[None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return ein("hqk,khd->qhd", probs, v)

    if q_block is None or q_block >= s:
        return rows(q, 0)
    if s % q_block:
        raise ValueError(f"q_block {q_block} does not divide the length {s}")
    out = jax.lax.map(lambda a: rows(*a), (
        q.reshape(s // q_block, q_block, *q.shape[1:]),
        jnp.arange(0, s, q_block)))
    return out.reshape(q.shape[0], *out.shape[2:])


def scan_layers(layer, x, blocks):
    """x through every layer of the stacked ``blocks``; each layer is
    recomputed in the backward pass so a whole-model gradient fits."""
    def body(x, blk):
        return jax.checkpoint(layer)(x, blk), None

    return jax.lax.scan(body, x, blocks)[0]
