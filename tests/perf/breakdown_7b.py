"""Step-time decomposition for the LLaMA-7B ZeRO-3 stand-in (full 7B layer
geometry, depth-scaled): fwd / fwd+bwd / trunk-only / head+loss, plus a
micro-batch sweep — the knobs BENCH_ALL's llama7b row is tuned with.

Run on the real chip: ``python tests/perf/breakdown_7b.py``.
"""

import time

import numpy as np



# transfer discipline: SIGTERM drains in-flight device work instead of dying
# mid-transfer (see deepspeed_tpu/utils/transfer.py)
from deepspeed_tpu.utils.transfer import install_transfer_guard

install_transfer_guard()

def timeit(fn, argsets, iters=10):
    """Fresh step-index per call defeats replay elision; one host sync at the
    end (per-call syncs would serialize the dispatches). NOTE: wall numbers
    carry ~7 ms of per-execution dispatch overhead when the loop is not
    pipelined — subtract the `dispatch floor` line when reading."""
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

    from deepspeed_tpu.models import TransformerLM, llama_config

    x = jnp.ones((8, 8), jnp.float32)
    print(f"dispatch floor       : "
          f"{timeit(jax.jit(lambda idx, a: a + idx), [(x,)]):8.2f} ms", flush=True)

    L, seq = 2, 2048
    for mb in (1, 2, 4):
        cfg = llama_config("7b", num_layers=L, max_seq_len=seq, remat=True,
                           remat_policy="dots")
        model = TransformerLM(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
        rng = np.random.default_rng(0)
        ids = [jnp.asarray(rng.integers(0, cfg.vocab_size - 64, (mb, seq),
                                        dtype=np.int32)) for _ in range(3)]
        p_args = [(params, i) for i in ids]
        g_fn = jax.jit(lambda idx, p, i: jax.grad(
            lambda pp: model.apply(pp, {"input_ids": i + idx % 7}, train=True))(p))
        t = timeit(g_fn, p_args)
        fl = cfg.flops_per_token(seq) * mb * seq
        print(f"mb={mb} fwd+bwd       : {t:8.2f} ms  "
              f"mfu(f+b-only)={fl / (t / 1e3) / 197e12:.3f}", flush=True)
        del params, p_args

    mb = 1
    cfg = llama_config("7b", num_layers=L, max_seq_len=seq, remat=True,
                       remat_policy="dots")
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    rng = np.random.default_rng(0)
    ids = [jnp.asarray(rng.integers(0, cfg.vocab_size - 64, (mb, seq),
                                    dtype=np.int32)) for _ in range(3)]
    p_args = [(params, i) for i in ids]

    f_fn = jax.jit(lambda idx, p, i: model.apply(
        p, {"input_ids": i + idx % 7}, train=True))
    print(f"mb=1 fwd(loss)       : {timeit(f_fn, p_args):8.2f} ms", flush=True)

    def trunk_loss(p, i):
        B, S = i.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        xh = model._embed(p, i, pos, jnp.bfloat16)
        xh, _ = model._trunk(p, xh, pos, None, True)
        return jnp.mean(xh.astype(jnp.float32))

    t_fn = jax.jit(lambda idx, p, i: jax.grad(
        lambda pp: trunk_loss(pp, i + idx % 7))(p))
    print(f"mb=1 fwd+bwd trunk   : {timeit(t_fn, p_args):8.2f} ms", flush=True)

    # Adam-only cost at this parameter count (the stand-in's fixed overhead)
    from deepspeed_tpu.ops.optimizers import FusedAdam

    opt = FusedAdam(lr=1e-4)
    master = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    state = opt.init(master)
    grads = jax.tree.map(lambda p: p * 0.001, master)

    def step(idx, g, s, m):
        g2 = jax.tree.map(lambda x: x * (1.0 + idx * 1e-6), g)
        return opt.update(g2, s, m, 1e-4)

    print(f"adam step ({sum(p.size for p in jax.tree.leaves(master)) / 1e6:.0f}M "
          f"params)  : {timeit(jax.jit(step), [(grads, state, master)]):8.2f} ms",
          flush=True)


if __name__ == "__main__":
    main()
