"""Step-time decomposition for the 350M bench config: fwd / fwd+bwd / full step,
and a truncated-loss variant to isolate the vocab-head + loss cost."""

import time

import numpy as np



# transfer discipline: SIGTERM drains in-flight device work instead of dying
# mid-transfer (see deepspeed_tpu/utils/transfer.py)
from deepspeed_tpu.utils.transfer import install_transfer_guard

install_transfer_guard()

def timeit(fn, argsets, iters=20):
    """fn takes (step_idx, *args); a fresh step_idx per call keeps the
    runtime from eliding identical replayed executions. One host sync at the
    end (per-call syncs would serialize the dispatches)."""
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

    from deepspeed_tpu.models import TransformerLM, gpt2_config

    seq, mb = 1024, 8
    cfg = gpt2_config("350m", max_seq_len=seq, remat=True, remat_policy="dots")
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    rng = np.random.default_rng(0)
    ids_list = [jnp.asarray(rng.integers(0, cfg.vocab_size - 64, (mb, seq),
                                         dtype=np.int32)) for _ in range(4)]
    p_args = [(params, i) for i in ids_list]

    loss_fn = jax.jit(lambda idx, p, i: model.apply(
        p, {"input_ids": i + idx % 7}, train=True))
    print(f"fwd(loss)            : {timeit(loss_fn, p_args):8.2f} ms", flush=True)

    g_fn = jax.jit(lambda idx, p, i: jax.grad(
        lambda pp: model.apply(pp, {"input_ids": i + idx % 7}, train=True))(p))
    print(f"fwd+bwd              : {timeit(g_fn, p_args):8.2f} ms", flush=True)

    # trunk only (mean of final hidden) — no vocab head, no loss
    def trunk_loss(p, i):
        B, S = i.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = model._embed(p, i, pos, jnp.bfloat16)
        x, _ = model._trunk(p, x, pos, None, True)
        return jnp.mean(x.astype(jnp.float32))

    t_fn = jax.jit(lambda idx, p, i: jax.grad(
        lambda pp: trunk_loss(pp, i + idx % 7))(p))
    print(f"fwd+bwd trunk-only   : {timeit(t_fn, p_args):8.2f} ms", flush=True)

    # head+loss only: trunk output detached (random hidden), head + CE loss
    xs = [jax.random.normal(jax.random.PRNGKey(i), (mb, seq, cfg.hidden_size),
                            jnp.bfloat16) for i in range(4)]

    def head_loss(p, xx, i):
        lg = model._head(p, xx).astype(jnp.float32)
        labels = jnp.concatenate([i[:, 1:], jnp.full_like(i[:, :1], -100)], axis=1)
        mask = labels != -100
        safe = jnp.where(mask, labels, 0)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1)

    h_fn = jax.jit(lambda idx, p, xx, i: jax.grad(head_loss)(
        p, xx + idx.astype(jnp.bfloat16) * 0.01, i))
    h_args = [(params, xs[i], ids_list[i]) for i in range(4)]
    print(f"fwd+bwd head+loss    : {timeit(h_fn, h_args):8.2f} ms", flush=True)


if __name__ == "__main__":
    main()
