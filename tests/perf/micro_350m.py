"""Microbenchmarks: dispatch overhead, bare matmul MFU, flash-attn cost."""

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

    # 1. dispatch overhead: trivial op
    x = jnp.ones((8, 8), jnp.float32)
    triv = jax.jit(lambda idx, a: a + idx)
    print(f"dispatch overhead    : {timeit(triv, [(x,)]):8.2f} ms", flush=True)

    # 2. matmul chain at model shapes: 24 x [(8192,1024)@(1024,4096)@(4096,1024)]
    a = jax.random.normal(jax.random.PRNGKey(0), (8192, 1024), jnp.bfloat16)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (24, 1024, 4096), jnp.bfloat16)
    w2 = jax.random.normal(jax.random.PRNGKey(2), (24, 4096, 1024), jnp.bfloat16)

    def mm(idx, a, w1, w2):
        h = a + idx.astype(jnp.bfloat16)

        def body(h, ws):
            u, d = ws
            return (h @ u) @ d, ()

        h, _ = jax.lax.scan(body, h, (w1, w2))
        return h

    mm_j = jax.jit(mm)
    t = timeit(mm_j, [(a, w1, w2)])
    fl = 24 * 2 * 2 * 8192 * 1024 * 4096
    print(f"matmul chain         : {t:8.2f} ms  mfu={fl / (t / 1e3) / 197e12:.3f}",
          flush=True)

    # 3. flash attention fwd at bench shapes (B=8,S=1024,h=16,d=64), 24 layers
    from deepspeed_tpu.ops.transformer.attention import attention

    q = jax.random.normal(jax.random.PRNGKey(3), (8, 1024, 16, 64), jnp.bfloat16)

    def att(idx, q):
        qq = q + idx.astype(jnp.bfloat16) * 0.01

        def body(h, _):
            return attention(h, h, h, causal=True), ()

        h, _ = jax.lax.scan(body, qq, None, length=24)
        return h

    att_j = jax.jit(att)
    t = timeit(att_j, [(q,)])
    fl = 24 * 2 * 2 * 8 * 16 * 1024 * 1024 * 64  # qk + av
    print(f"flash attn x24 fwd   : {t:8.2f} ms  mfu={fl / (t / 1e3) / 197e12:.3f}",
          flush=True)

    # 4. same via xla impl
    def attx(idx, q):
        qq = q + idx.astype(jnp.bfloat16) * 0.01

        def body(h, _):
            return attention(h, h, h, causal=True, impl="xla"), ()

        h, _ = jax.lax.scan(body, qq, None, length=24)
        return h

    t = timeit(jax.jit(attx), [(q,)])
    print(f"xla attn x24 fwd     : {t:8.2f} ms  mfu={fl / (t / 1e3) / 197e12:.3f}",
          flush=True)


if __name__ == "__main__":
    main()
