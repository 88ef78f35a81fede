"""A padding row of a latent-attention step is dead for attention too.

``forward_paged`` of both latent configurations (``deepseek_v3`` and the
double layer) gives its live rows the same logits with padding rows and a
padding tile as without them, and every ``mla_decode*`` call of its program
is handed ``limits`` 0 for a row with the zero table, from limits computed
once a step. The kernel's side is ``test_mla_decode_live_context.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import TransformerLM
from deepspeed_tpu.ops.transformer import paged_attention as pa
from tests.unit.test_served_weight_reads import (double_layers, latent,
                                                 layer_scans, step)


def with_padding(args, kw, rows, tile_rows, pad_rows, pad_tiles):
    """The step of ``args`` with ``pad_rows`` padding rows behind its
    one-token rows and ``pad_tiles`` padding tiles behind its tile rows: the
    zeroed feed (token 0, position 0, the zero table)."""
    params, ids, pool, tables, starts = args

    def padded(a, fill=0):
        a = np.asarray(a)
        one = np.full((pad_rows,) + a.shape[1:], fill, a.dtype)
        tile = np.full((pad_tiles * pa.SEGMENT_TILE,) + a.shape[1:], fill,
                       a.dtype)
        return jnp.asarray(np.concatenate([a[:rows], one, a[rows:], tile]))

    kw = dict(kw)
    if tile_rows:
        kw["seg_from"] = rows + pad_rows
    live = np.concatenate([np.arange(rows),
                           rows + pad_rows + np.arange(tile_rows)])
    return (params, padded(ids), pool, padded(tables), padded(starts)), kw, live


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gather"])
@pytest.mark.parametrize("tile_rows", [0, pa.SEGMENT_TILE],
                         ids=["round", "segment_tile"])
@pytest.mark.parametrize("make", [latent, double_layers],
                         ids=["latent", "double_layers"])
def test_padding_rows_move_no_live_logit(monkeypatch, make, tile_rows, kernel):
    """``forward_paged`` with padding rows and a padding tile against the
    same call without them: the live rows' logits and every pool block a
    sequence holds are the same."""
    if kernel:
        monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    else:
        monkeypatch.delenv("DSTPU_FORCE_PAGED_KERNEL", raising=False)
    model = TransformerLM(make())
    rows = 3
    args, kw = step(model, rows=rows, tile_rows=tile_rows)
    kw["rows_apart"] = False
    want, want_pool = jax.jit(
        lambda *a: model.forward_paged(*a, **kw))(*args)
    padded, pkw, live = with_padding(args, kw, rows, tile_rows, pad_rows=3,
                                     pad_tiles=1 if tile_rows else 0)
    got, got_pool = jax.jit(
        lambda *a: model.forward_paged(*a, **pkw))(*padded)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_pool)[:, :, 1:],
                                  np.asarray(want_pool)[:, :, 1:])


def producers(jaxpr, var, seen=None):
    """The primitives that ``var`` is computed by inside ``jaxpr``, back to
    its inputs."""
    seen = set() if seen is None else seen
    made_by = {o: e for e in jaxpr.eqns for o in e.outvars}
    eqn = made_by.get(var)
    if eqn is None:
        return seen
    seen.add(eqn.primitive.name)
    for v in eqn.invars:
        if hasattr(v, "count"):          # a Var, not a Literal
            producers(jaxpr, v, seen)
    return seen


@pytest.mark.parametrize("make", [latent, double_layers],
                         ids=["latent", "double_layers"])
def test_every_mla_decode_call_gets_limit_zero_for_a_padding_row(
        monkeypatch, make):
    """A mixed step with padding rows and a padding tile. In the program
    every layer body holds one ``mla_decode`` and one ``mla_decode_segment``
    a (sub)layer, and what says how many blocks a cell fetches is computed
    from limits that enter the body from outside (the ``where`` on the
    table is a step's, not a layer's). Run, every call of every layer is
    handed ``limits`` 0 for a row with the zero table and position + 1 for a
    live one."""
    monkeypatch.setenv("DSTPU_FORCE_PAGED_KERNEL", "1")
    model = TransformerLM(make())
    rows, tile_rows, pad_rows = 3, pa.SEGMENT_TILE, 2
    args, kw = step(model, rows=rows, tile_rows=tile_rows)
    kw["rows_apart"] = False
    padded, pkw, live = with_padding(args, kw, rows, tile_rows,
                                     pad_rows=pad_rows, pad_tiles=1)
    handed = []
    real = pa.mla_decode

    def spy(q_lat, q_rope, pool, layer, tables, limits, **kws):
        jax.debug.callback(
            lambda lim, q=kws.get("q_tile", 1): handed.append(
                (q, np.asarray(lim))), limits)
        return real(q_lat, q_rope, pool, layer, tables, limits, **kws)

    monkeypatch.setattr(pa, "mla_decode", spy)
    run = lambda *a: model.forward_paged(*a, **pkw)     # noqa: E731
    jaxpr = jax.make_jaxpr(run)(*padded).jaxpr
    per_body = model.config.pool_layers // model.config.num_layers
    scans = list(layer_scans(jaxpr))
    assert len(scans) == len(model.config.type_runs)
    for scan in scans:
        body = scan.params["jaxpr"].jaxpr
        calls = [e for e in body.eqns if e.primitive.name == "pallas_call"
                 and e.params["name"].startswith("mla_decode")]
        assert sorted(e.params["name"] for e in calls) == sorted(
            ["mla_decode", "mla_decode_segment"] * per_body)
        for call in calls:
            # operands of the one-token call: layer, tables, each row's
            # limit, q_lat, q_rope, the pool; of the segment call: layer,
            # tables, each cell's blocks, q_lat, q_rope, each row's limit,
            # the pool
            said = (2,) if call.params["name"] == "mla_decode" else (2, 5)
            for operand in (call.invars[i] for i in said):
                assert "select_n" not in producers(body, operand)

    jax.block_until_ready(jax.jit(run)(*padded))
    jax.effects_barrier()
    tables, starts = np.asarray(padded[3]), np.asarray(padded[4])
    want = np.where(tables[:, 0] > 0, starts + 1, 0)
    cut = rows + pad_rows
    assert not want[rows:cut].any() and not want[-tile_rows:].any()
    assert (want[live] > 0).all()
    assert len(handed) == 2 * model.config.pool_layers
    for q_tile, lim in handed:
        np.testing.assert_array_equal(
            lim, want[:cut] if q_tile == 1 else want[cut:])
