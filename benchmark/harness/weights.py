"""Weights and token ids from ``--seed``, made on the device in one jitted
call each. The seed is a traced argument, so every seed runs the same compiled
program."""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def split_seed(seed):
    """A seed of up to 63 bits as two int32 words."""
    seed = int(seed)
    return np.int32(seed & 0x7FFFFFFF), np.int32((seed >> 31) & 0x7FFFFFFF)


def _key(lo, hi, stream):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(lo), hi),
                              stream)


@functools.lru_cache(maxsize=None)
def _weights_fn(treedef, shapes, names, std, n_layers, shardings):
    def make(lo, hi):
        key = _key(lo, hi, 0)
        leaves = []
        for i, (shape, name) in enumerate(zip(shapes, names)):
            # residual-branch projections get the depth-scaled init
            scale = std / np.sqrt(2 * n_layers) if name in ("wo", "w_down") \
                else std
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale
            leaves.append(1.0 + x if name.endswith("_scale") else x)
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(make, out_shardings=(
        None if shardings is None else jax.tree.unflatten(treedef, shardings)))


def make_weights(abstract, seed, std, n_layers, shardings=None):
    """Float32 weights in the tree ``abstract`` (shapes only): every matrix
    and bias normal with the configuration's ``std`` (residual projections
    scaled by 1/sqrt(2L)), every LayerNorm scale 1 + such noise, so no term
    of the model is left at zero. ``shardings``: optional tree of shardings."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = tuple(str(getattr(p[-1], "key", p[-1])) for p, _ in flat)
    shapes = tuple(tuple(leaf.shape) for _, leaf in flat)
    sh = None if shardings is None else tuple(jax.tree.leaves(shardings))
    return _weights_fn(treedef, shapes, names, std, n_layers, sh)(
        *split_seed(seed))


@functools.lru_cache(maxsize=None)
def _ids_fn(shape, vocab):
    return jax.jit(lambda lo, hi, stream: jax.random.randint(
        _key(lo, hi, stream), shape, 0, vocab, jnp.int32))


def make_ids(seed, stream, shape, vocab):
    """Seeded token ids on the device. ``stream`` separates the uses of one
    seed (1: training batches)."""
    return _ids_fn(tuple(shape), int(vocab))(*split_seed(seed), np.int32(stream))


def spread(abstract, mesh):
    """Shardings that split every leaf over all devices of ``mesh`` on its
    first dimension they divide (small leaves stay whole): how the reference's
    float32 copy of a model too large for one chip is held."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n, axes = mesh.size, tuple(mesh.axis_names)

    def one(leaf):
        for d, size in enumerate(leaf.shape):
            if leaf.ndim >= 2 and size % n == 0:
                return NamedSharding(mesh, P(*([None] * d + [axes])))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, abstract)
