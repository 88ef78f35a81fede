"""The router's choice alone (logits given): ``group_limited_gating`` and
``softmax_topk_gating`` (``moe/sharded_moe.py``) at the four expert cells'
shapes, the rule said with sorts beside the tree's form, and the two smaller
selections of a routed layer each in both of its forms.

A call is one expert layer's choice for a round: ``rows`` rows of float32
logits over ``E`` router outputs and a selection bias. The call is made
``--calls`` times in one jitted ``fori_loop`` (the logits shifted by the trip,
so that nothing is hoisted) and a line says what a call took. PERF.md 5's
table of the router was made so (PR 72); no benchmark cell runs this.

Forms, ``us_a_call`` each:

- ``router.sort``: the rule as the program wrote it until PR 72, a group's
  score ``sum(top_k(group, 2))`` and the kept groups ``top_k(score, n)``: on
  the chip a ``top_k`` is a full sort of the array;
- ``router.tree``: the tree's function (``--tree`` for another checkout's),
  with ``same``: its picks and weights are the sorts' bit for bit;
- ``pick.top_k`` | ``pick.argmax`` | ``pick.maxmin``: the ``k`` picks over
  ``E`` biased scores alone, by ``lax.top_k``, by ``k`` rounds of first
  ``argmax`` and mask, and by ``k`` rounds of a maximum and the least index
  that holds it (two plain reductions a round);
- ``part.argsort`` | ``part.ranks``: ``grouped_experts``' list of the touched
  experts first (``moe/layer.py``), a stable partition of ``held`` flags, by
  ``argsort`` and by ranks from two cumulative sums.

    python examples/kernels/route_alone.py                     # four cells
    python examples/kernels/route_alone.py --tree <dir>        # another checkout's router
    python examples/kernels/route_alone.py --cell reason --rows 128,512

Times mean something on a TPU only; ``JAX_PLATFORMS=cpu`` with ``--tiny``
rehearses the flow.
"""

import argparse
import json
import os
import sys
import time

#: cell -> (rows of a round, router outputs, n_group, topk_group, k, held
#: experts, router)
CELLS = {"reason": (128, 512, 8, 4, 8, 64, "group_limited"),
         "longdoc": (32, 256, 8, 4, 8, 16, "group_limited"),
         "win16k": (32, 128, 1, 1, 8, 16, "group_limited"),
         "longout": (96, 768, 1, 1, 12, 8, "softmax_topk")}
TINY = {"reason": (16, 64, 4, 2, 4, 8, "group_limited"),
        "longdoc": (8, 32, 4, 2, 4, 4, "group_limited"),
        "win16k": (8, 16, 1, 1, 4, 4, "group_limited"),
        "longout": (8, 48, 1, 1, 6, 4, "softmax_topk")}


def router_by_sort(logits, bias, *, router, k, n_group, topk_group):
    """Both routers with every selection a ``lax.top_k``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    T, E = logits.shape
    logits = logits.astype(jnp.float32)
    if router == "softmax_topk":
        s = jax.nn.softmax(logits, axis=-1)
        biased = s + bias / E
    else:
        s = jax.nn.sigmoid(logits)
        biased = s + bias
    if n_group > 1:
        by_group = biased.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
        kept = lax.top_k(group_score, topk_group)[1]
        group_ok = jnp.any(
            kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        biased = jnp.where(jnp.repeat(group_ok, E // n_group, axis=1),
                           biased, -jnp.inf)
    chosen = lax.top_k(biased, k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(s, chosen, axis=1)
    if router == "group_limited":
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w


def picks(form, biased, k):
    """(T, k) int32: the ``k`` largest of each row in ``top_k``'s order (ties
    to the lower index), for rows that hold ``k`` scores above ``-inf``."""
    import jax.numpy as jnp
    from jax import lax

    if form == "top_k":
        return lax.top_k(biased, k)[1].astype(jnp.int32)
    E = biased.shape[1]
    cols = jnp.arange(E, dtype=jnp.int32)[None, :]
    out = []
    for _ in range(k):
        if form == "argmax":
            i = jnp.argmax(biased, axis=-1).astype(jnp.int32)
        else:
            top = jnp.max(biased, axis=-1, keepdims=True)
            i = jnp.min(jnp.where(biased == top, cols, E), axis=-1)
        out.append(i)
        biased = jnp.where(cols == i[:, None], -jnp.inf, biased)
    return jnp.stack(out, axis=1)


def partition(form, counts):
    """(E,) int32: the experts some row chose first, each part in order."""
    import jax.numpy as jnp

    empty = counts == 0
    if form == "argsort":
        return jnp.argsort(empty, stable=True).astype(jnp.int32)
    E = counts.shape[0]
    live = jnp.cumsum(~empty, dtype=jnp.int32)
    rank = jnp.where(empty, live[-1] + jnp.cumsum(empty, dtype=jnp.int32),
                     live) - 1
    e = jnp.arange(E, dtype=jnp.int32)
    return jnp.sum(jnp.where(rank[None, :] == e[:, None], e[None, :], 0),
                   axis=1, dtype=jnp.int32)


def timed(fn, args, calls, reps):
    """us a call of ``fn(trip, *args) -> (rows,) float32`` summed over
    ``calls`` trips of one jitted loop; the best of ``reps``."""
    import jax
    import jax.numpy as jnp

    def run(*args):
        first = fn(jnp.int32(0), *args)
        return jax.lax.fori_loop(
            0, calls, lambda i, acc: acc + fn(i, *args),
            jnp.zeros_like(first))

    jitted = jax.jit(run)
    jitted(*args).block_until_ready()           # compiles
    best = float("inf")
    for _ in range(reps):
        start = time.perf_counter()
        jitted(*args).block_until_ready()
        best = min(best, time.perf_counter() - start)
    return round(best / calls * 1e6, 2)


def measure(cell, shape, rows, forms, tree_router, calls, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    _, E, n_group, topk_group, k, held, router = shape
    rng = np.random.default_rng(rows + E)
    logits = jnp.asarray(rng.normal(size=(rows, E)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(E,)) * 0.1, jnp.float32)
    kw = dict(k=k, n_group=n_group, topk_group=topk_group)
    base = {"cell": cell, "rows": rows, "outputs": E, "n_group": n_group,
            "k": k, "device": jax.devices()[0].device_kind}

    def shift(i):       # other scores every trip: nothing is hoisted
        return (i % 16).astype(jnp.float32) * 1e-3

    def whole(route):
        def call(i, logits, bias):
            chosen, w = route(logits + shift(i), bias)
            return jnp.sum(chosen, axis=-1) + jnp.sum(w, axis=-1)
        return call

    def by_sort(l, b):
        return router_by_sort(l, b, router=router, **kw)

    def by_tree(l, b):
        if router == "softmax_topk":
            return tree_router[router](l, b, k=k)
        return tree_router[router](l, b, **kw)

    def same(got, want):
        return all(bool(jnp.array_equal(a, b)) for a, b in zip(got, want))

    def line(form, call, args, **more):
        print(json.dumps(dict(base, form=form, us_a_call=timed(
            call, args, calls, reps), **more)), flush=True)

    if "router.sort" in forms:
        line("router.sort", whole(by_sort), (logits, bias))
    if "router.tree" in forms:
        line("router.tree", whole(by_tree), (logits, bias),
             same=same(jax.jit(by_tree)(logits, bias),
                       jax.jit(by_sort)(logits, bias)))

    # the picks alone: the scores as they reach them, the groups not kept
    # already at -inf
    masked = jnp.asarray(np.where(
        np.repeat(rng.permutation(n_group) < topk_group, E // n_group)[None],
        rng.uniform(size=(rows, E)), -np.inf), jnp.float32)
    want = jax.jit(lambda m: picks("top_k", m, k))(masked)
    for form in ("top_k", "argmax", "maxmin"):
        if f"pick.{form}" not in forms:
            continue

        def call(i, masked, form=form):
            return jnp.sum(picks(form, masked + shift(i), k),
                           axis=-1).astype(jnp.float32)

        line(f"pick.{form}", call, (masked,), same=same(
            [jax.jit(lambda m, form=form: picks(form, m, k))(masked)],
            [want]))

    counts = jnp.asarray(rng.integers(0, 3, size=(held,)), jnp.int32)
    want = partition("argsort", counts)
    for form in ("argsort", "ranks"):
        if f"part.{form}" not in forms:
            continue

        def call(i, counts, form=form):
            # other experts stand empty every trip
            c = jnp.where((jnp.arange(held) + i) % 3 == 0, 0, counts)
            return partition(form, c).astype(jnp.float32)

        line(f"part.{form}", call, (counts,), held=held,
             same=same([partition(form, counts)], [want]))


FORMS = ("router.sort,router.tree,pick.top_k,pick.argmax,pick.maxmin,"
         "part.argsort,part.ranks")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", help="another checkout's package and router")
    ap.add_argument("--cell", default=",".join(CELLS))
    ap.add_argument("--rows", help="rows of a call, comma separated "
                    "(default: the cell's round)")
    ap.add_argument("--forms", default=FORMS)
    ap.add_argument("--calls", type=int, default=120)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(
        args.tree or os.path.join(os.path.dirname(__file__), "..", "..")))

    from deepspeed_tpu.moe import sharded_moe

    tree_router = {"group_limited": sharded_moe.group_limited_gating,
                   "softmax_topk": sharded_moe.softmax_topk_gating}
    forms = args.forms.split(",")
    for cell in args.cell.split(","):
        shape = (TINY if args.tiny else CELLS)[cell]
        for rows in ([int(n) for n in args.rows.split(",")] if args.rows
                     else [shape[0]]):
            measure(cell, shape, rows, forms, tree_router,
                    8 if args.tiny else args.calls,
                    1 if args.tiny else args.reps)


if __name__ == "__main__":
    main()
