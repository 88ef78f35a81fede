"""What decides ``correct``: the program against the plain reference.

The reference gets the seed's float32 weights and the seed's token ids and
nothing the program has made. The training reference holds the whole float32
tree (a training cell's state is 14 bytes a parameter: its float32 copy is not
what caps the model). The serving reference and the weights comparison never
do: they draw one layer, or one slice, at a time (``weights.Seeded``), so a
served model may fill the chip. Every comparison yields one number that is
printed beside its limit; the limits live in the configuration file
(``tolerances``) with the readings they were set from.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import ein_f32


def architecture(config):
    return importlib.import_module(
        f"benchmark.reference.{config['architecture']}")


#: per-layer leaves whose first update is compared: a matrix on the attention
#: path, one on the MLP path, a bias and a LayerNorm scale
UPDATE_LEAVES = ("wq", "w_down", "mlp_up_bias", "ln1_scale")


def train_reference(config, weights, batch, ein=ein_f32, devices=None):
    """(loss, gradients of ``UPDATE_LEAVES``) of one optimizer step's batch
    (N, S): next-token cross entropy averaged over all N*(S-1) predicted
    tokens, one sequence at a time, gradients accumulated in float32. On
    several ``devices`` each takes N/len(devices) of the sequences against a
    whole copy of the weights."""
    arch = architecture(config)
    n_dev = len(devices) if devices else 1
    sub = {k: weights["blocks"][k] for k in UPDATE_LEAVES}

    def seq_loss(sub, w, ids):
        w = {**w, "blocks": {**w["blocks"], **sub}}
        lg = arch.logits(w, arch.hidden(w, ids, config, ein), ein)[:-1]
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, ids[1:, None], axis=-1)[:, 0]
        return jnp.mean(nll)

    def whole(sub, w, batch):
        def row(seqs):
            def body(acc, ids):
                loss, grads = jax.value_and_grad(seq_loss)(sub, w, ids)
                return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], grads)), None

            zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, sub))
            return jax.lax.scan(body, zero, seqs)[0]

        loss, grads = jax.vmap(row)(batch.reshape(n_dev, -1, batch.shape[-1]))
        n = batch.shape[0]
        return loss.sum() / n, jax.tree.map(lambda g: g.sum(0) / n, grads)

    if n_dev > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devices), ("all",))
        sub, weights = jax.device_put((sub, weights), NamedSharding(mesh, P()))
        batch = jax.device_put(batch, NamedSharding(mesh, P("all")))
    loss, grads = jax.jit(whole)(sub, weights, batch)
    return float(loss), grads


def update_sign_mismatch(before, after, ref_grads, lr, weight_decay):
    """How far the program's first AdamW update of ``UPDATE_LEAVES`` departs
    from the reference gradient. At step 1 AdamW moves every element by
    ``-lr * (g / (|g| + eps) + weight_decay * p)``, so the update (less its
    decay term) carries the sign of the program's own gradient, element by
    element, after forward, backward, clipping and the optimizer. The number
    is the share of the reference gradient's L1 mass on elements whose sign
    the program got wrong: noise of relative size s flips a share of about
    s**2 / 4, so it grows with the square of the precision lost."""
    @jax.jit
    def measure(before, after, ref_grads):
        num = den = 0.0
        for k, g in ref_grads.items():
            moved = -((after[k] - before[k]) / lr + weight_decay * before[k])
            num += jnp.sum(jnp.abs(g) * (moved * g < 0))
            den += jnp.sum(jnp.abs(g))
        return num / den

    return float(measure(before, after, ref_grads))


def serve_reference(config, seeded, ids, rows, ein=ein_f32):
    """Logits (K, R, V) of K padded sequences ``ids`` (K, T) at the R
    positions ``rows`` (K, R) of each: the full causal forward, so padding
    after a position cannot reach it. The model is walked layer by layer:
    layer ``l`` of ``seeded`` (a ``weights.Seeded``) is drawn in float32,
    applied by the once-compiled layer to the hidden states of all K
    sequences (one sequence at a time), and dropped before the next is
    drawn: the host waits for each layer, so that no queue of drawn layers
    stands on the chip.

    Where the configuration says ``"router_bias": "balanced"``, an expert
    layer's selection bias is not drawn but fitted, on the way, to this
    pass's own tokens (every position up to a sequence's last row), so that
    they choose every router output equally often (``arch.balanced_bias``),
    and left in ``seeded.made``: the served tree, its comparison and a
    later pass (the control's) then hold that same bias."""
    arch = architecture(config)
    ids, rows = jnp.asarray(ids), jnp.asarray(rows)
    balanced = config.get("router_bias") == "balanced"
    if config.get("router_bias") not in (None, "balanced"):
        raise ValueError(f"reference: router_bias {config['router_bias']!r}")

    def over(f):
        return jax.jit(lambda w, *xs: jax.lax.map(lambda a: f(w, *a), xs))

    x = over(lambda w, seq: arch.embed(w, seq, config))(seeded.unstacked(), ids)
    layer = over(lambda b, x: arch.layer(x, b, config, ein))
    if balanced:
        attend = over(lambda b, x: arch.attend(x, b, config, ein))
        feed = over(lambda b, x: arch.feed(x, b, config, ein))
        # the sequences' lengths are an argument: one program for every seed
        fit = jax.jit(lambda b, x, last: _balanced_bias(arch, config, b, x,
                                                        last))
    for group, n in arch.groups(config):
        if n != seeded.layers(group):
            raise ValueError(f"reference: '{group}' has {seeded.layers(group)} "
                             f"layers of weights, the configuration {n}")
        for l in range(n):
            b = seeded.layer(group, l)
            if balanced and "moe_bias" in b and (group, l) not in seeded.made:
                x = attend(b, x)
                made = seeded.made[group, l] = {
                    "moe_bias": fit(b, x, rows[:, -1])}
                x = feed({**b, **made}, x).block_until_ready()
            else:
                x = layer(b, x).block_until_ready()
            del b               # before the next layer is drawn
    head = over(lambda w, x, pos: arch.logits(
        w, arch.final(w, x, config)[pos], ein))
    return np.asarray(head(seeded.unstacked(), x, rows))


#: the most positions a selection bias is fitted to: the fit's passes cost
#: by the position, and 8192 of them put each of 256 outputs' share within a
#: few per cent
FIT_POSITIONS = 8192


def _balanced_bias(arch, config, b, x, last):
    """The balanced selection bias of layer ``b``'s router over the positions
    up to ``last`` (K,) of the hidden states ``x`` (K, T, H) as ``attend``
    left them, every n-th of them where they are more than ``FIT_POSITIONS``;
    the scores in float32 at ``highest``, whatever pass fits."""
    s = jax.lax.map(lambda a: arch.router_scores(a, b, config, ein_f32), x)
    s = s.reshape(-1, s.shape[-1])
    valid = (jnp.arange(x.shape[1])[None, :] <= last[:, None]).reshape(-1)
    if len(s) > FIT_POSITIONS:
        every = -(-jnp.sum(valid) // FIT_POSITIONS)
        valid &= (jnp.cumsum(valid) - 1) % every == 0
        kept = jnp.argsort(~valid, stable=True)[:FIT_POSITIONS]
        s, valid = s[kept], valid[kept]
    return arch.balanced_bias(s, valid, config)


def logits_rel_err(got, want):
    """Root-mean-square error of ``got`` against ``want`` over the spread of
    ``want`` about each row's mean (the part of a logit row that decides
    anything)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    spread = want - want.mean(axis=-1, keepdims=True)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(spread ** 2)))


def rel_err(got, want):
    return abs(float(got) - float(want)) / abs(float(want))


@jax.jit
def _differ(served, drawn, l):
    """Per leaf: does layer ``l`` of ``served`` (the whole leaf, ``l`` None)
    differ anywhere from ``drawn``? Both arrive in the served dtype: no
    convert in this program (``weights.cast``)."""
    return {k: jnp.any((x if l is None else x[l]) != drawn[k])
            for k, x in served.items()}


def weights_mismatch_share(served, seeded, dtype):
    """Share of the served tree's leaves that are not, bit for bit, the
    seed's weights rounded to the configuration's dtype (1.0 if the trees
    differ in structure, as a quantised tree does). ``seeded`` (a
    ``weights.Seeded``) draws each layer again, by the programs that built
    the served tree, to compare it."""
    if jax.tree.structure(served) != seeded.treedef:
        return 1.0
    n = len(jax.tree.leaves(served))
    bad = sum(x.dtype != dtype for x in jax.tree.leaves(served))
    if bad:
        return bad / n
    flags = list(_differ({k: served[k] for k in seeded.top},
                         seeded.unstacked(dtype), None).values())
    for group in seeded.groups:
        worst = None
        for l in range(seeded.layers(group)):
            now = _differ(served[group], seeded.layer(group, l, dtype),
                          np.int32(l))
            worst = now if worst is None else jax.block_until_ready(
                jax.tree.map(jnp.logical_or, worst, now))
        flags += list(worst.values())
    return sum(bool(f) for f in flags) / n


class Verdict:
    """Collects (name, value, limit) rows; ``correct`` when every value is
    finite and within its limit."""

    def __init__(self, tolerances):
        self.tolerances = tolerances
        self.rows = []

    def add(self, name, value):
        limit = self.tolerances[name]["limit"]
        ok = bool(np.isfinite(value)) and value <= limit
        self.rows.append((name, float(value), limit, ok))
        print(f"[check] {name} = {value:.6g}  (limit {limit:g})  "
              f"{'ok' if ok else 'NOT CORRECT'}", flush=True)

    @property
    def correct(self):
        return bool(self.rows) and all(ok for *_, ok in self.rows)
