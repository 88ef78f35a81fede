"""Weights and token ids from ``--seed``, made on the device. The seed is a
traced argument, so every seed runs the same compiled programs.

The unit of drawing is a slice. Leaf ``i`` of the flattened tree has the key
``fold_in(key, i)``; a leaf of three or more dimensions is drawn slice by
slice along its first axis, slice ``j`` under ``fold_in(fold_in(key, i), j)``,
and a leaf of one or two dimensions is drawn whole. So stacked per-layer
matrices ``(L, in, out)`` come a layer at a time, and ``Seeded`` can hand out
the same bits three ways: the whole float32 tree in one jitted call
(training), the whole tree in the serving dtype built layer by layer (peak:
that tree plus one layer), and one layer of a stacked group in float32 (the
serving reference). The rule reads a leaf's shape and name and nothing else:
no configuration names a key or a size.

A layer of a group is one call of one compiled float32 program, whatever the
seed or the layer, shared by the reference, the served tree and its
comparison: a run traces and loads a handful of programs, not one per leaf
and dtype (on the chip's host tracing them, and not the drawing, is what costs
seconds: PERF.md, PR 29)."""

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


def _sliced(shape):
    return len(shape) >= 3


def _draw(key, shape, scale, one_plus):
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return 1.0 + x if one_plus else x


def _draw_leaves(key, rules, l=None):
    """The leaves ``rules`` ((leaf index, shape, scale, one_plus) each) under
    ``key``: whole (a sliced leaf as its slices stacked), or with ``l`` their
    layer-``l`` parts (slice ``l`` of a sliced leaf, row ``l`` of a leaf drawn
    whole). One draw a leaf: on the chip's host a program costs what its
    draws cost to trace (~0.1 s each), and leaves of one shape drawn together
    under ``vmap`` traced three times slower (PERF.md, PR 29)."""
    fold, out = jax.random.fold_in, []
    for i, shape, scale, one_plus in rules:
        k = fold(key, i)
        if not _sliced(shape):
            x = _draw(k, shape, scale, one_plus)
            out.append(x if l is None else x[l])
        elif l is None:
            out.append(jax.vmap(lambda j: _draw(fold(k, j), shape[1:], scale,
                                                one_plus))(jnp.arange(shape[0])))
        else:
            out.append(_draw(fold(k, l), shape[1:], scale, one_plus))
    return out


@functools.lru_cache(maxsize=None)
def _tree_fn(treedef, rules, shardings):
    def make(lo, hi):
        return jax.tree.unflatten(treedef,
                                  _draw_leaves(_key(lo, hi, 0), rules))

    return jax.jit(make, out_shardings=(
        None if shardings is None else jax.tree.unflatten(treedef, shardings)))


@functools.lru_cache(maxsize=None)
def _parts_fn(rules, layer):
    """Float32 leaves ``rules``, whole or (``layer``) their layer-``l``
    parts: one compiled program, whatever the seed or the layer."""
    if layer:
        return jax.jit(lambda lo, hi, l: _draw_leaves(_key(lo, hi, 0), rules, l))
    return jax.jit(lambda lo, hi: _draw_leaves(_key(lo, hi, 0), rules))


@functools.partial(jax.jit, static_argnums=1)
def cast(tree, dtype):
    """``tree`` rounded to ``dtype``, in a program of its own: a convert
    inside the program that compares or consumes its result may be elided on
    the TPU (excess precision), and the comparison then sees the unrounded
    float32."""
    return jax.tree.map(lambda x: x.astype(dtype), tree)


@functools.partial(jax.jit, donate_argnums=0)
def _put_layer(bufs, parts, l):
    return {k: jax.lax.dynamic_update_index_in_dim(buf, parts[k], l, 0)
            for k, buf in bufs.items()}


class Seeded:
    """The seed's weights in the tree ``abstract`` (shapes only; a dict of
    leaves and of stacked groups, each a dict of leaves with the layer axis
    first): every matrix and bias normal with the configuration's ``std``
    (residual projections ``wo`` / ``w_down`` scaled by 1/sqrt(2L)), every
    ``*_scale`` 1 + such noise, so no term of the model is left at zero."""

    def __init__(self, abstract, seed, std, n_layers):
        self.abstract = abstract
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(abstract)
        paths = [tuple(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
        if any(len(p) > 2 for p in paths):
            raise ValueError("weights: a tree deeper than {group: {leaf}}")
        # residual-branch projections get the depth-scaled init
        self.rules = tuple(
            (i, tuple(leaf.shape), float(std / np.sqrt(2 * n_layers)
                                         if p[-1] in ("wo", "w_down") else std),
             p[-1].endswith("_scale"))
            for i, (p, (_, leaf)) in enumerate(zip(paths, flat)))
        self.seed = split_seed(seed)
        #: {group: {leaf name: index}} of the stacked groups, {leaf name:
        #: index} of the leaves outside them, in the tree's own order
        self.groups, self.top = {}, {}
        for i, p in enumerate(paths):
            (self.top if len(p) == 1 else self.groups.setdefault(p[0], {}))[
                p[-1]] = i
        #: {(group, layer): {leaf name: float32 value}}: leaves that are not
        #: drawn but made from the seed's other weights and its inputs (a
        #: fitted selection bias: ``check.serve_reference``). ``layer`` and
        #: ``tree_as`` hand them out in the drawn ones' place; ``tree`` does
        #: not know them
        self.made = {}

    def tree(self, shardings=None):
        """The whole float32 tree, one jitted call. ``shardings``: optional
        tree of shardings."""
        sh = None if shardings is None else tuple(jax.tree.leaves(shardings))
        return _tree_fn(self.treedef, self.rules, sh)(*self.seed)

    def layers(self, group):
        """How many layers the stacked ``group`` has."""
        (n,) = {self.rules[i][1][0] for i in self.groups[group].values()}
        return n

    def layer(self, group, l, dtype=jnp.float32):
        """Layer ``l`` of the stacked ``group`` (a top-level key of the tree):
        {leaf name: its layer-``l`` part}, drawn in float32 and rounded to
        ``dtype``."""
        index = self.groups[group]
        rules = tuple(self.rules[i] for i in index.values())
        drawn = dict(zip(index, _parts_fn(rules, True)(*self.seed, np.int32(l))))
        for k, x in self.made.get((group, l), {}).items():
            if x.shape != drawn[k].shape or x.dtype != jnp.float32:
                raise ValueError(f"weights: made leaf '{group}.{k}' of layer "
                                 f"{l} is {x.dtype}{x.shape}")
            drawn[k] = x
        return self._as(drawn, dtype)

    def unstacked(self, dtype=jnp.float32):
        """The leaves that sit at the top of the tree, outside every stacked
        group (embeddings, final norm, output head), drawn in float32 and
        rounded to ``dtype``."""
        rules = tuple(self.rules[i] for i in self.top.values())
        return self._as(dict(zip(self.top, _parts_fn(rules, False)(*self.seed))),
                        dtype)

    @staticmethod
    def _as(drawn, dtype):
        return drawn if jnp.dtype(dtype) == jnp.float32 \
            else cast(drawn, jnp.dtype(dtype))

    def tree_as(self, dtype):
        """The whole tree in ``dtype``, each layer drawn in float32, cast and
        written into its leaves' buffers in place: the peak is this tree plus
        one float32 layer. The host waits for each write, so that no queue of
        layers stands beside the tree."""
        out = self.unstacked(dtype)
        for group, index in self.groups.items():
            bufs = {k: jnp.zeros(self.rules[i][1], dtype)
                    for k, i in index.items()}
            for l in range(self.layers(group)):
                bufs = jax.block_until_ready(_put_layer(
                    bufs, self.layer(group, l, dtype), np.int32(l)))
            out[group] = bufs
        return out


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
