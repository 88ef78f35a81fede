"""Compare in-tree flash kernel vs jax.experimental TPU kernels at bench shapes."""

import functools
import time

import numpy as np



# transfer discipline: SIGTERM drains in-flight device work instead of dying
# mid-transfer (see deepspeed_tpu/utils/transfer.py)
from deepspeed_tpu.utils.transfer import install_transfer_guard

install_transfer_guard()

def timeit(fn, argsets, iters=20):
    import jax

    def force(o):
        leaf = jax.tree.leaves(o)[0]
        np.asarray(jax.device_get(leaf.ravel()[0]))

    for w, a in enumerate(argsets[:2]):
        force(fn(np.int32(1000 + w), *a))
    t0 = time.perf_counter()
    out = None
    for i in range(iters):
        out = fn(np.int32(i), *argsets[i % len(argsets)])
    force(out)
    return (time.perf_counter() - t0) / iters * 1000


def main():
    import jax
    import jax.numpy as jnp

    B, nh, S, hd = 8, 16, 1024, 64
    L = 24
    q = jax.random.normal(jax.random.PRNGKey(3), (B, nh, S, hd), jnp.bfloat16)
    fl_fwd = L * 2 * 2 * B * nh * S * S * hd / 2  # causal: half the blocks
    fl_bwd = fl_fwd * 3.5 / 1.0  # dq+dkv recompute ≈ 2.5x fwd + fwd itself

    def report(name, t, fl):
        print(f"{name:28s}: {t:8.2f} ms  causal-mfu={fl / (t / 1e3) / 197e12:.3f}",
              flush=True)

    # --- in-tree kernel (B,S,h,d surface) ---
    from deepspeed_tpu.ops.transformer.attention import attention

    def mine_f(idx, q):
        qq = (q + idx.astype(jnp.bfloat16) * 0.01).transpose(0, 2, 1, 3)

        def body(h, _):
            return attention(h, h, h, causal=True), ()

        h, _ = jax.lax.scan(body, qq, None, length=L)
        return h

    report("mine fwd", timeit(jax.jit(mine_f), [(q,)]), fl_fwd)

    def mine_g(idx, q):
        qq = (q + idx.astype(jnp.bfloat16) * 0.01).transpose(0, 2, 1, 3)

        def loss(x):
            def body(h, _):
                return attention(h, h, h, causal=True), ()

            h, _ = jax.lax.scan(body, x, None, length=L)
            return jnp.sum(h.astype(jnp.float32) * 1e-3)

        return jax.grad(loss)(qq)

    report("mine fwd+bwd", timeit(jax.jit(mine_g), [(q,)]), fl_fwd + fl_bwd)

    # --- jax flash_attention (B,nh,S,hd surface) ---
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention)

    bs = BlockSizes(
        block_q=512, block_k_major=512, block_k=512, block_b=1,
        block_q_major_dkv=512, block_k_major_dkv=512, block_k_dkv=512,
        block_q_dkv=512, block_k_major_dq=512, block_k_dq=512, block_q_dq=512,
    )
    fa = functools.partial(flash_attention, causal=True, sm_scale=hd ** -0.5,
                           block_sizes=bs)

    def jf_f(idx, q):
        qq = q + idx.astype(jnp.bfloat16) * 0.01

        def body(h, _):
            return fa(h, h, h), ()

        h, _ = jax.lax.scan(body, qq, None, length=L)
        return h

    report("jax flash fwd", timeit(jax.jit(jf_f), [(q,)]), fl_fwd)

    def jf_g(idx, q):
        qq = q + idx.astype(jnp.bfloat16) * 0.01

        def loss(x):
            def body(h, _):
                return fa(h, h, h), ()

            h, _ = jax.lax.scan(body, x, None, length=L)
            return jnp.sum(h.astype(jnp.float32) * 1e-3)

        return jax.grad(loss)(qq)

    report("jax flash fwd+bwd", timeit(jax.jit(jf_g), [(q,)]), fl_fwd + fl_bwd)

    # --- splash attention ---
    try:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk, splash_attention_mask as sm)

        mask = sm.CausalMask((S, S))
        mgrid = sm.MultiHeadMask([mask] * nh)
        kernel = sk.make_splash_mha(
            mask=mgrid, head_shards=1, q_seq_shards=1)

        def sp_f(idx, q):
            qq = q + idx.astype(jnp.bfloat16) * 0.01
            scale = hd ** -0.5

            def body(h, _):
                o = jax.vmap(kernel)(h * scale, h, h)
                return o.astype(h.dtype), ()

            h, _ = jax.lax.scan(body, qq, None, length=L)
            return h

        report("splash fwd", timeit(jax.jit(sp_f), [(q,)]), fl_fwd)

        def sp_g(idx, q):
            qq = q + idx.astype(jnp.bfloat16) * 0.01
            scale = hd ** -0.5

            def loss(x):
                def body(h, _):
                    o = jax.vmap(kernel)(h * scale, h, h)
                    return o.astype(h.dtype), ()

                h, _ = jax.lax.scan(body, x, None, length=L)
                return jnp.sum(h.astype(jnp.float32) * 1e-3)

            return jax.grad(loss)(qq)

        report("splash fwd+bwd", timeit(jax.jit(sp_g), [(q,)]), fl_fwd + fl_bwd)
    except Exception as e:
        print(f"splash failed: {type(e).__name__}: {str(e)[:200]}", flush=True)


if __name__ == "__main__":
    main()
