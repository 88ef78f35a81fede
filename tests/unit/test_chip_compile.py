"""The main path's kernels, compiled for the real chip at GPT-2 350M widths —
no chip attached.

``test_tpu_lowering.py`` exports StableHLO, which stops before the Mosaic
compiler's checks of fast memory and tiling. Here the TPU compiler that ships
with libtpu compiles each kernel for a *described* v5e (on-chip-measurement
guide, section 2), both called directly and reached through the dispatch the
model uses (``attention()``, the paged branch of ``forward_paged``), with the
``jax.default_backend()`` gate steered inside the test. Shapes are the ones
``chip_smoke.py`` runs: flash attention at (8, 1024, 16, 64) bf16; paged
decode at its server's pool (129 blocks of 64) and block tables (16 wide), at
both row counts of the serving program (8 and 256). The serving program
itself is compiled at the benchmark cell's engine geometry (832 blocks of 64,
rows 64 and 256) for heads of 64 and of 128, and searched for any operation
that moves a layer's pool or more: the pool is written in place and read
where it lies.

All of it lives in this one file, and the topology is described inside a
fixture: only the worker that runs this file loads libtpu, and every worker
collects the same tests.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, S, NH, HD = 8, 1024, 16, 64          # the trainer's attention shape
NB, BS, MAXB = 129, 64, 16              # the server's pool and block tables
ROWS = (8, 256)                         # decode round, mixed prefill+decode
CELL_NB, CELL_SEQS = 832, 64            # the serve-chat cell's pool and rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the code's backend gates (``pallas_interpret``, ``_auto_impl``,
    the paged branch) the way the chip would: they ask
    ``jax.default_backend()``, which here still answers "cpu"."""
    monkeypatch.delenv("DSTPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def compile_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


def aval(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def sq_loss(attn):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
    return loss


@pytest.mark.parametrize("via", ["direct", "attention()"])
@pytest.mark.parametrize("pass_", ["fwd", "fwd+bwd"])
def test_flash_attention_compiles(one_chip, no_compile_cache, as_tpu, via,
                                  pass_):
    from deepspeed_tpu.ops.transformer.attention import attention
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

    def attn(q, k, v):
        if via == "direct":
            return flash_attention(q, k, v, causal=True)
        return attention(q, k, v, causal=True)

    fn = attn if pass_ == "fwd" else jax.grad(sq_loss(attn), argnums=(0, 1, 2))
    q = aval(one_chip, (B, S, NH, HD), jnp.bfloat16)
    text = compile_text(fn, q, q, q)
    # forward is one kernel, the fused backward another
    assert text.count("tpu_custom_call") >= (1 if pass_ == "fwd" else 2), \
        "flash attention gave way to the XLA path"


@pytest.mark.parametrize("shape", [(2, S, NH, HD), (4, 2048, 16, 128)],
                         ids=["gpt2-medium", "pythia-1.4b"])
def test_a_kernel_programs_build_record_names_its_kernels(
        one_chip, no_compile_cache, as_tpu, shape):
    """The always-on account (docs/TRACING.md "Set-up and recompiles") of a
    program lowered and compiled for the described chip, under the 16 MiB of
    VMEM the compiler gives unasked: one record, made by the test (no
    ``site``), with both flash kernels by name, how often each was bound
    while the program was traced and what tracing their bodies cost; lowering
    them to Mosaic is inside ``lower_s``. Each kernel's bind says how many
    scores a head multiplies beside the causal pairs it needs: the 128 x 128
    tiles on and under the diagonal (1.124 x the pairs at 1024; the parent's
    one masked square a chunk read 1.50 forward and 1.25 backward)."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    from deepspeed_tpu.utils import tracing

    def kernel_program(q, k, v):
        return sq_loss(lambda *a: flash_attention(*a, causal=True))(q, k, v)

    mark = tracing.clock_ns()
    q = aval(one_chip, shape, jnp.bfloat16)
    compile_text(jax.grad(kernel_program, argnums=(0, 1, 2)), q, q, q)
    rec, = [r for r in tracing.builds()
            if r.end > mark and "kernel_program" in r.attrs["program"]]
    a = rec.attrs
    assert a["site"] == "" and not a["cached"]
    assert a["trace_s"] > 0 and a["lower_s"] > 0 and a["load_s"] > 0
    assert set(a["kernels"]) == {"flash_fwd", "flash_bwd"}
    for calls, seconds in a["kernels"].values():
        assert calls == 1 and 0 < seconds < a["trace_s"]
    s, tiles = shape[1], shape[1] // 128
    assert a["kernel_attrs"] == dict.fromkeys(
        ("flash_fwd", "flash_bwd"),
        {"pairs_computed": 128 * 128 * tiles * (tiles + 1) // 2,
         "pairs_causal": s * (s + 1) // 2})
    if s == 1024:
        pairs = a["kernel_attrs"]["flash_fwd"]
        assert pairs["pairs_computed"] == 589_824
        assert round(pairs["pairs_computed"] / pairs["pairs_causal"], 3) == 1.124


#: a ``copy`` or ``transpose`` of the compiled program (at the top level or
#: inside a fusion) and its result's dimensions
LAYOUT_MOVE = re.compile(r"= \w+\[([\d,]+)\]\S* (?:copy|transpose)\(")


@pytest.mark.parametrize("shape", [(B, S, NH, HD), (4, 2048, 16, 128)],
                         ids=["gpt2-medium", "pythia-1.4b"])
def test_flash_moves_no_head_layout(one_chip, no_compile_cache, as_tpu, shape):
    """The kernels address attention where the model keeps it: q, k, v arrive
    as ``(B, S, heads * head_dim)`` (a product's output), ``attention()`` gets
    the free 4-D view of them, and forward and backward compile to the two
    custom calls with no ``copy`` or ``transpose`` of an array the size of q
    anywhere in the program (the parent transposed q, k, v, o, do and dq, dk,
    dv to ``(B, heads, S, head_dim)`` and back: twelve a layer)."""
    from deepspeed_tpu.ops.transformer.attention import attention

    b, s, nh, hd = shape

    def loss(q, k, v):
        out = attention(*(x.reshape(shape) for x in (q, k, v)), causal=True)
        return jnp.sum(out.reshape(b, s, nh * hd).astype(jnp.float32) ** 2)

    x = aval(one_chip, (b, s, nh * hd), jnp.bfloat16)
    text = compile_text(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    moved = [dims for dims in LAYOUT_MOVE.findall(text)
             if math.prod(map(int, dims.split(","))) == math.prod(shape)]
    assert not moved, moved


@pytest.fixture(scope="module")
def gpt2_layer_step(one_chip, no_compile_cache):
    """One layer of the ``gpt2-medium.train-seq1024`` job (q/k/v biases, remat
    ``dots``, layers unrolled, as the cell's files say) under
    ``value_and_grad``, compiled for the described chip: its text."""
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    model = TransformerLM(gpt2_config(
        "350m", num_layers=1, qkv_bias=True, remat=True, remat_policy="dots",
        scan_layers=False))
    params = jax.tree.map(
        lambda a: aval(one_chip, a.shape, jnp.bfloat16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))

    def loss(params, batch):
        out = model.apply(params, batch, train=True)
        return out[0] if isinstance(out, tuple) else out

    with pytest.MonkeyPatch.context() as patch:   # as_tpu, at module scope
        patch.delenv("DSTPU_PALLAS_INTERPRET", raising=False)
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return compile_text(
            jax.value_and_grad(loss), params,
            {"input_ids": aval(one_chip, (B, S), jnp.int32)})


def test_a_gpt2_layer_holds_no_head_layout_copy(gpt2_layer_step):
    """The compiled step holds no ``copy bf16[8,1024,16,64]``. The parent of
    PR 47 held twelve a layer: q, k, v into the kernel's
    ``(B, heads, S, head_dim)`` and the result back, the same again under
    remat, ``do`` in and dq, dk, dv back. Its four kernel calls are
    ``flash_fwd`` and ``flash_bwd`` of the one layer and the head's
    ``fused_ce_fwd`` and ``fused_ce_bwd``."""
    text = gpt2_layer_step
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    for kernel in ("flash_fwd", "flash_bwd", "fused_ce_fwd", "fused_ce_bwd"):
        assert f"/{kernel}/pallas_call" in text, kernel
    per_head = f"= bf16[{B},{S},{NH},{HD}]"
    copies = [line.strip()[:120] for line in text.splitlines()
              if per_head in line and " copy(" in line]
    assert not copies, copies


VOCAB = 50304


def vocab_sized(text, tokens=B * S, vocab=VOCAB):
    """``(instruction, dtype)`` of every array of tokens x vocabulary elements
    that an instruction of the entry computation writes (a fusion's inner
    instructions write none; a ``get-tuple-element`` or ``bitcast`` names one
    that is there)."""
    found = []
    for line in text[text.index("ENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z\-]+)\(", line)
        if not m or m.group(3) in ("get-tuple-element", "bitcast", "parameter"):
            continue
        found += [(m.group(1), dtype)
                  for dtype, dims in re.findall(r"([a-z0-9]+)\[([0-9,]+)\]",
                                                m.group(2))
                  if math.prod(map(int, dims.split(","))) == tokens * vocab]
    return found


def test_a_gpt2_step_holds_one_vocabulary_sized_array(gpt2_layer_step):
    """The head's loss keeps one ``[tokens, vocab]`` array, the bf16 logits:
    the parent wrote them in float32 too, 1.65 GB a step, for a gather of
    8,192 numbers."""
    arrays = vocab_sized(gpt2_layer_step)
    assert [dtype for _, dtype in arrays] == ["bf16"], arrays


def test_the_head_leaves_the_layers_activations_where_they_lie(gpt2_layer_step):
    """At most two ``copy bf16[8,1024,1024]`` in the one-layer step (the saved
    ``o`` to ``{1,2,0}`` for ``wo``'s weight gradient and back for
    ``flash_bwd``). The parent held seven, and 76 + 24 + 24 copies in the 24
    layers of the cell's step for 48: autodiff's head took the activations
    three-dimensional, its weight product asked for them transposed, and
    layout assignment carried that choice into every layer's backward; that,
    not the head's own 3.9 ms, was most of what PR 56 gained (PERF.md 5)."""
    copies = re.findall(rf"= bf16\[{B},{S},{NH * HD}\]\S* copy\(",
                        gpt2_layer_step)
    assert len(copies) <= 2, copies


#: sha256 of the one-layer GPT-2 step's feed-forward (``sublayer_lines`` of
#: the ``mlp`` scope, joined by newlines) at commit 30574ee (PR 57): the tanh
#: form's products, forward, saved and backward. PR 58 gave ``"gelu_exact"``
#: its own unit and left this expression letter for letter
TANH_MLP_SHA = ("a2fb595041280b2f89d7700e64a49e0e"
                "ab4b2c86b46f5315c479ad846381c512")


def test_the_tanh_layer_compiles_to_what_it_was(gpt2_layer_step):
    import hashlib

    lines = sublayer_lines(gpt2_layer_step, scopes=("mlp",))
    assert any(" tanh(" in line for line in lines)
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()
    assert digest == TANH_MLP_SHA


PYTHIA_MLP = (4, 2048, 8192)    # a chip's tokens of the four-chip cell x 4H


def computations(text):
    """``{name: [instruction, ...]}`` of optimized HLO: every fused
    computation (an operand's prologue is one of its own, called from the
    product's) and the entry."""
    found, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            lines = found.setdefault(head.group(1), [])
        elif lines is not None:
            lines.append(line.strip())
    return found


def activation_evaluations(text, shape=PYTHIA_MLP):
    """``[(has a product, arrays of ``shape`` it returns)]`` for every
    computation that holds an ``exponential`` or a ``divide`` over an array
    of ``shape``: where the exact GELU is evaluated."""
    dims = ",".join(map(str, shape))
    found = []
    for lines in computations(text).values():
        costly = rf"= f32\[{dims}\]\S* (exponential|divide)\("
        if not any(re.search(costly, line) for line in lines):
            continue
        returns = next(re.match(r"ROOT \S+ = (.*?) [a-z\-]+\(", line).group(1)
                       for line in lines if line.startswith("ROOT "))
        found.append((any(" convolution(" in line for line in lines),
                      len(re.findall(rf"bf16\[{dims}\]", returns))))
    return sorted(found)


@pytest.fixture(scope="module")
def pythia_block(one_chip, no_compile_cache):
    """One block at Pythia-1.4B's widths over a chip's tokens of the four-chip
    cell: ``(forward's text, text of jax.grad of the checkpointed block)``."""
    model = serving_model(128, 1)
    blk = jax.tree.map(
        lambda a: aval(one_chip, a.shape[1:], jnp.bfloat16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0))["blocks"])
    x = aval(one_chip, PYTHIA_MLP[:2] + (model.config.hidden_size,),
             jnp.bfloat16)

    def block(blk, x):
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return model._block(x, blk, positions=positions, rng=None,
                            train=True)[0]

    def loss(blk, x, d):
        return jnp.sum((jax.checkpoint(block)(blk, x) * d).astype(jnp.float32))

    with pytest.MonkeyPatch.context() as patch:   # as_tpu, at module scope
        patch.delenv("DSTPU_PALLAS_INTERPRET", raising=False)
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return (compile_text(block, blk, x),
                compile_text(jax.grad(loss, argnums=(0, 1)), blk, x, x))


def test_the_exact_gelu_is_evaluated_once_a_pass(pythia_block):
    """``exponential`` and ``divide`` over ``[4,2048,8192]`` stand in the
    fusion that makes ``h`` (``x @ w_up + b``, the activation its epilogue)
    and nowhere else: once in the forward, which returns ``a`` alone, and
    once in the backward's rematerialised forward, which returns ``a`` and
    ``g`` (``jax.grad`` of a loss linear in the block's output keeps no
    other forward). The products that read them (``a @ w_down``,
    ``w_down``'s gradient, ``d_out @ w_down^T``) hold neither. The parent
    evaluated ``erfc``'s expansion in the prologue of the first two and the
    epilogue of the third: 59-65% of peak on the chip for their neighbours'
    91-95%."""
    forward, backward = pythia_block
    assert activation_evaluations(forward) == [(True, 1)]
    assert activation_evaluations(backward) == [(True, 2)]


def test_the_forward_writes_one_array_of_the_mlps_width(pythia_block):
    """No derivative and no second ``[tokens, 4H]`` array in the forward:
    the entry computation writes ``a`` and nothing else of that size (``h``
    stays inside the product's fusion)."""
    arrays = vocab_sized(pythia_block[0], tokens=math.prod(PYTHIA_MLP[:2]),
                         vocab=PYTHIA_MLP[2])
    assert [dtype for _, dtype in arrays] == ["bf16"], arrays


@pytest.mark.parametrize("tokens, width, vocab_major, kernels", [
    ((8, 1024), 1024, True, 2),     # gpt2-medium.train-seq1024: both kernels
    ((4, 2048), 2048, False, 1),    # pythia-1.4b, lm_head (2048, 50304): the
])                                  # backward's accumulator does not fit VMEM
def test_the_heads_unit_compiles(one_chip, no_compile_cache, as_tpu, tokens,
                                 width, vocab_major, kernels):
    from deepspeed_tpu.ops.transformer.fused_ce import head_nll

    def loss(x, w, labels):
        return jnp.mean(head_nll(x, w, labels, vocab_major=vocab_major))

    text = compile_text(
        jax.value_and_grad(loss, argnums=(0, 1)),
        aval(one_chip, (*tokens, width), jnp.bfloat16),
        aval(one_chip, (VOCAB, width) if vocab_major else (width, VOCAB),
             jnp.bfloat16),
        aval(one_chip, tokens, jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    arrays = vocab_sized(text, math.prod(tokens))
    assert [dtype for _, dtype in arrays] == ["bf16"], arrays


# (rows, query heads, kv heads, pool blocks, head size, table width, kv heads
# a cell, blocks a trip): the chat server's decode round and mixed step, and
# serve-doc16k's sparse view (a (row, kv head) pair a row of the pool with its
# kv heads folded into its blocks, 64 chosen 32 KB blocks a row: four trips)
PAGED_CALLS = [(rows, NH, NH, NB, HD, MAXB, NH, 1) for rows in ROWS] + [
    (96, 16, 1, 27136, 128, 64, 1, 16),
    # serve-chat's own pool and round, serve-win16k's two classes of blocks
    (CELL_SEQS, NH, NH, CELL_NB, HD, MAXB, NH, 1),
    (32, 32, 4, 608, 128, 42, 4, 4), (32, 32, 4, 2048, 128, 274, 4, 4)]


@pytest.mark.parametrize("rows,nh,kvh,nb,hd,maxb,hpc,bpt", PAGED_CALLS)
def test_paged_decode_compiles(one_chip, no_compile_cache, as_tpu, rows, nh,
                               kvh, nb, hd, maxb, hpc, bpt):
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    pool = aval(one_chip, (kvh, nb, BS, hd), jnp.bfloat16)
    stacked = jax.ShapeDtypeStruct((1, kvh, nb, BS, 2 * hd), jnp.bfloat16)
    assert (pa.heads_per_cell(stacked), pa.blocks_per_trip(stacked)) == (
        hpc, bpt)
    assert pa.rows_per_cell(rows) == min(rows, 32)
    text = compile_text(
        pa.paged_decode_attention,
        aval(one_chip, (rows, nh, hd), jnp.bfloat16), pool, pool,
        aval(one_chip, (rows, maxb), jnp.int32),
        aval(one_chip, (rows,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,rows,dtype", [
    ((24, NH, CELL_NB, BS, 2 * HD), CELL_SEQS, jnp.bfloat16),  # serve-chat
    ((24, NH, 416, BS, 256), CELL_SEQS, jnp.bfloat16),   # Pythia-1.4B, hd 128
    ((5, 1, 3072, BS, 640), 32, jnp.bfloat16),    # serve-longdoc's latent pool
    ((8, 1, 2560, BS, 640), 96, jnp.bfloat16),    # serve-longout's
    ((4, 4, NB, 16, 2 * HD), 8, jnp.float32),     # a float32 pool, blocks of 16
])
def test_kv_write_compiles(one_chip, no_compile_cache, as_tpu, shape, rows,
                           dtype):
    """The live-row write takes the sub-tile form Mosaic accepts (a one-row
    slice of a bfloat16 pool's token dimension it refuses), and writes the
    donated pool where it lies."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    pool = aval(one_chip, shape, dtype)
    assert pa.writes_live_rows(pool)
    i32 = functools.partial(aval, one_chip, dtype=jnp.int32)
    compiled = jax.jit(pa.kv_write, donate_argnums=(0,)).lower(
        pool, i32(()), i32((rows,)), i32((rows,)),
        aval(one_chip, (rows, shape[1], shape[4]), dtype)).compile()
    text = compiled.as_text()
    assert re.search(r"%kv_write[.\d]* = \S+ custom-call\(", text)
    assert pool_sized_movers(text, math.prod(shape) // shape[0]) == []
    assert "may-alias" in text or "must-alias" in text, \
        "the donated pool is no longer written in place"


@pytest.mark.parametrize("shape,rows,g,dtype", [
    ((24, NH, CELL_NB, BS, 2 * HD), CELL_SEQS, 1, jnp.bfloat16),  # serve-chat
    ((24, NH, 416, BS, 256), CELL_SEQS, 1, jnp.bfloat16),  # Pythia-1.4B
    ((10, 4, 608, BS, 256), 32, 8, jnp.bfloat16),   # serve-win16k, window
    ((3, 4, 2048, BS, 256), 32, 8, jnp.bfloat16),   # serve-win16k, full
    ((2, 8, NB, BS, 512), 256, 1, jnp.bfloat16),    # heads of 256
    ((4, 2, NB, 16, 2 * HD), 8, 4, jnp.bfloat16),   # grouped queries
    ((4, 4, NB, 16, 256), 8, 4, jnp.float32),       # a float32 pool
])
def test_paged_decode_with_new_rows_compiles(one_chip, no_compile_cache,
                                             as_tpu, shape, rows, g, dtype):
    """The decode round's one call: Mosaic takes the heads cut out of the
    lane layout, the row set in the fetched block and the sub-tile's store,
    and the donated pool is written where it lies."""
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    pool = aval(one_chip, shape, dtype)
    assert pa.writes_live_rows(pool)
    kvh, hd = shape[1], shape[4] // 2

    def call(pool, q, k, v, layer, tables, lens):
        return pa.paged_decode(q, pool, layer, tables, lens, new_rows=(k, v))

    lanes = lambda n: aval(one_chip, (rows, n * hd), jnp.bfloat16)
    i32 = functools.partial(aval, one_chip, dtype=jnp.int32)
    text = jax.jit(call, donate_argnums=(0,)).lower(
        pool, lanes(kvh * g), lanes(kvh), lanes(kvh), i32(()),
        i32((rows, MAXB)), i32((rows,))).compile().as_text()
    assert re.search(r"%paged_decode[.\d]* = \S+ \S+ custom-call\(", text)
    assert pool_sized_movers(text, math.prod(shape) // shape[0]) == []
    assert "may-alias" in text or "must-alias" in text, \
        "the donated pool is no longer written in place"


@pytest.mark.parametrize("shape,rows,g,maxb,form", [
    ((10, 4, 608, BS, 256), 32, 8, 42, "write+bounded"),   # serve-win16k
    ((3, 4, 2048, BS, 256), 32, 8, 274, "write"),
    ((10, 4, 608, BS, 256), 32, 8, 42, "read+bounded"),    # its mixed step
    ((8, 1, 27136, BS, 256), 96, 16, 64, "read"),          # serve-doc16k
    ((24, NH, CELL_NB, BS, 2 * HD), CELL_SEQS, 1, MAXB, "write"),  # serve-chat
], ids=lambda v: v if isinstance(v, str) else None)
def test_a_bfloat16_pools_trip_multiplies_what_it_fetched(shape, rows, g, maxb,
                                                          form):
    """The kernel's traced body at the cells' shapes: both products of a trip
    take bfloat16 operands and give float32, and no array of a trip's tokens
    is converted from bfloat16 to float32 (the parent converted the whole
    ``(hpc, bpt * BS, 2 * hd)`` buffer every trip)."""
    from deepspeed_tpu.analysis.program_audit import _iter_eqns
    from deepspeed_tpu.ops.transformer import paged_attention as pa

    kvh, hd = shape[1], shape[4] // 2
    pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    bound = (lambda f: {"first": f}) if "bounded" in form else (lambda f: {})
    if "write" in form:
        lanes = lambda n: jax.ShapeDtypeStruct((rows, n * hd), jnp.bfloat16)

        def call(pool, q, k, v, tables, lens, first):
            return pa.paged_decode(q, pool, 1, tables, lens, new_rows=(k, v),
                                   **bound(first))
        args = (pool, lanes(kvh * g), lanes(kvh), lanes(kvh))
    else:
        def call(pool, q, tables, lens, first):
            return pa.paged_decode(q, pool, 1, tables, lens, **bound(first))
        args = (pool, jax.ShapeDtypeStruct((rows, kvh * g, hd), jnp.bfloat16))
    traced = jax.make_jaxpr(call)(*args, i32(rows, maxb), i32(rows), i32(rows))
    (bind,) = [e for e in traced.eqns if e.primitive.name == "pallas_call"]
    eqns = list(_iter_eqns(bind.params["jaxpr"]))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [jnp.bfloat16] * 2
        assert dot.outvars[0].aval.dtype == jnp.float32
    tokens = pa.blocks_per_trip(pool) * BS
    widened = [e.invars[0].aval.shape for e in eqns
               if e.primitive.name == "convert_element_type"
               and e.invars[0].aval.dtype == jnp.bfloat16
               and e.outvars[0].aval.dtype == jnp.float32
               and e.invars[0].aval.shape[-2:-1] == (tokens,)]
    assert widened == []


@pytest.mark.parametrize("rows", ROWS)
def test_paged_branch_of_the_model_compiles(one_chip, no_compile_cache, as_tpu,
                                            rows):
    """One layer of GPT-2 350M through ``forward_paged``, as the serving
    program calls it: every row one query token against the block pool."""
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    model = TransformerLM(gpt2_config("350m", num_layers=1))

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: aval(one_chip, a.shape, dtype or a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    pool = on_chip(jax.eval_shape(
        lambda: model.init_kv_pool(NB, BS, dtype=jnp.bfloat16)))
    text = compile_text(
        model.forward_paged, params, aval(one_chip, (rows, 1), jnp.int32),
        pool, aval(one_chip, (rows, MAXB), jnp.int32),
        aval(one_chip, (rows,), jnp.int32))
    assert "tpu_custom_call" in text, \
        "paged decode took the XLA gather path at the smoke's own shapes"


_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = ([a-z0-9]+)\[([0-9,]*)\]\S* ([\w\-]+)\(")
#: what moves an array: a pool-sized result of one of these is a pool copy
_MOVERS = ("copy", "dynamic-slice", "dynamic-update-slice", "transpose",
           "concatenate", "pad", "slice", "gather")


def pool_sized_movers(text, elems):
    """Instructions of optimized HLO (fused computations included) that move
    an array of at least ``elems`` elements."""
    found = []
    for line in text.splitlines():
        m = _HLO_RESULT.match(line)
        if not m or m.group(3) not in _MOVERS:
            continue
        if math.prod(int(d) for d in m.group(2).split(",") if d) >= elems:
            found.append(line.strip()[:160])
    return found


def serving_model(head_dim, layers):
    from deepspeed_tpu.models import (TransformerConfig, TransformerLM,
                                      gpt2_config)

    if head_dim == 64:      # GPT-2 350M, the cell's own configuration
        return TransformerLM(gpt2_config("350m", num_layers=layers))
    return TransformerLM(TransformerConfig(   # Pythia-1.4B's widths
        vocab_size=50304, hidden_size=2048, num_layers=layers, num_heads=16,
        intermediate_size=8192, max_seq_len=2048, pos_embedding="rope",
        rotary_dim=32, norm="layernorm", activation="gelu_exact",
        parallel_block=True, tie_embeddings=False, qkv_bias=True))


def serving_avals(one_chip, model, rows, blocks=CELL_NB, block_size=BS,
                  tables=MAXB):
    """(params, pool, tables, starts) of the cell's engine, as shapes."""
    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: aval(one_chip, a.shape, dtype or a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
                     jnp.bfloat16)
    pool = on_chip(jax.eval_shape(
        lambda: model.init_kv_pool(blocks, block_size, dtype=jnp.bfloat16)))
    return (params, pool, aval(one_chip, (rows, tables), jnp.int32),
            aval(one_chip, (rows,), jnp.int32))


def assert_moves_no_pool(compiled, pool, layers):
    """No ``copy``, slice, update-slice or transpose whose result holds a
    layer's pool or more, no pool-sized temporary, the pool aliased from
    input to output, the kernel in the program."""
    text, pools = compiled.as_text(), jax.tree.leaves(pool)
    layer_elems = min(a.size for a in pools) // layers
    assert pool_sized_movers(text, layer_elems) == []
    assert compiled.memory_analysis().temp_size_in_bytes < \
        layer_elems * pools[0].dtype.itemsize
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry_", text)
    assert aliases and aliases.group(1).count("alias)") == len(pools), \
        "the donated pool is no longer updated in place"
    assert "tpu_custom_call" in text


def sublayer_lines(text, scopes=("kv_write", "paged_attn")):
    """The instructions of optimized HLO that were traced under ``scopes``,
    sorted, without what a change elsewhere in the program moves: the
    instructions' numbering, their metadata (source lines) and a kernel's
    serialised body (it holds source lines too)."""
    lines = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if not name or not any(f"/{s}/" in name.group(1) for s in scopes):
            continue
        line = re.sub(r"backend_config=.*", "", line)
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        lines.append(re.sub(r"%([A-Za-z_\-]+)[.\d]*", r"%\1", line).strip())
    return sorted(lines)


#: sha256 of the mixed step's attention sublayer (``sublayer_lines`` joined
#: by newlines) at commit d279223 (PR 52), by head size: the scatter of
#: ``write_rows``, the relaid q, ``paged_decode`` on ``(rows, kvh, g, hd)``
#: and the result's way back. PR 53 folded the decode round's write into its
#: attention kernel and left this step to the PR that takes the scatter out
#: of it (``ROADMAP.md`` S1): that PR re-pins these
MIXED_SUBLAYER_SHA = {
    64: "744de346a7e044cc9ae0b1ac58e29e95d1e70b2129899098314e3bed7b2550d7",
    128: "fe7193fbc1de6f1ca6c864e8428f037607180af39695310c59a5aabf02971951",
}


@pytest.mark.parametrize("head_dim,layers,rows", [
    (64, 4, CELL_SEQS), (64, 2, 256), (128, 2, CELL_SEQS), (128, 3, 256)])
def test_serving_program_moves_no_pool(one_chip, no_compile_cache, as_tpu,
                                       head_dim, layers, rows):
    """The ragged program (``forward_paged`` over one-token rows, only the
    sequences' rows projected, pool donated) at the cell's pool, for both
    row counts and for heads of 64 and of 128. The decode round's attention
    sublayer is ONE kernel a layer body, which takes q and gives its result
    as ``(rows, heads * head_dim)``: no q relaid a (row, head) a tile, no
    ``reduce`` over the unit head-group axis behind the call, no
    ``kv_write``. The mixed step's sublayer is what it was."""
    import hashlib

    model = serving_model(head_dim, layers)
    assert model.config.head_dim == head_dim
    params, pool, tables, starts = serving_avals(one_chip, model, rows)

    def ragged(params, pool, ids, tables, starts, logit_rows):
        # as the engine calls it: the decode round's rows are apart
        return model.forward_paged(params, ids, pool, tables, starts,
                                   logit_rows=logit_rows,
                                   rows_apart=rows == CELL_SEQS)

    compiled = jax.jit(ragged, donate_argnums=(1,)).lower(
        params, pool, aval(one_chip, (rows, 1), jnp.int32), tables, starts,
        aval(one_chip, (CELL_SEQS,), jnp.int32)).compile()
    assert_moves_no_pool(compiled, pool, layers)
    text = compiled.as_text()
    # the round's rows ride in ``paged_decode``, the mixed step scatters
    assert not re.search(r"%kv_write[.\d]* = \S+ custom-call\(", text)
    # GPT-2's tied table is streamed by ``head_logits``; Pythia's untied head
    # is XLA's product
    tied = model.config.tie_embeddings
    assert text.count('custom_call_target="tpu_custom_call"') == 1 + tied
    assert bool(re.search(r"%head_logits[.\d]* = \S+ custom-call\(",
                          text)) == tied
    if rows != CELL_SEQS:
        digest = hashlib.sha256(
            "\n".join(sublayer_lines(text)).encode()).hexdigest()
        assert digest == MIXED_SUBLAYER_SHA[head_dim]
        return
    per_head = rf"\[{rows},{NH},1,{head_dim}\]\{{[^}}]*T\(2,128\)"
    assert not re.findall(per_head, text)
    ungrouped = [line.strip()[:120] for line in text.splitlines()
                 if re.search(rf"= \w+\[{rows},{NH},{head_dim}\]\S* reduce\(",
                              line)]
    assert not ungrouped, ungrouped
    call = re.search(r"%paged_decode[.\d]* = \((\w+\[[\d,]+\])", text)
    assert call and call.group(1) == f"bf16[{rows},{NH * head_dim}]"


@pytest.fixture(scope="module")
def chat_engine():
    """``gpt2-medium.serve-chat``'s engine (its traffic file's geometry) over
    two of the model's layers: the programs scan the layers, so their number
    changes nothing outside the loop, where the table is used."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    model = serving_model(64, 2)
    return InferenceEngineV2(
        model, model.init_params(jax.random.PRNGKey(0)), dtype=jnp.bfloat16,
        max_seqs=CELL_SEQS, max_seq_len=1024, block_size=BS, token_budget=256,
        prefill_chunk=128, num_blocks=CELL_NB)


@pytest.mark.parametrize("rows, greedy", [
    (CELL_SEQS, True), (CELL_SEQS, False), (256, False)])
def test_serve_chats_programs_copy_no_table_into_vmem(
        one_chip, no_compile_cache, as_tpu, chat_engine, rows, greedy):
    """The engine's own ragged programs (the decode round with the sampler
    behind it, and both shapes returning logits) for the described v5e: the
    tied table is gathered from and streamed where it lies in HBM. XLA's own
    product made it the program's cross-program prefetch: 103 MB copied to
    VMEM that the program waited for in full (PERF.md 5, ``serve-chat``)."""
    engine = chat_engine

    def on_chip(tree):
        return jax.tree.map(lambda a: aval(one_chip, a.shape, a.dtype), tree)

    vocab, width = engine.params["wte"].shape
    text = engine._get_ragged().lower(
        on_chip(engine.params), on_chip(engine.kv),
        aval(one_chip, (engine._feed_layout(rows)[1],), jnp.int32),
        aval(one_chip, engine._prev_shape(), jnp.int32),
        aval(one_chip, (CELL_SEQS, vocab), jnp.float32), greedy,
    ).compile().as_text()
    assert engine._program_attrs["ragged"] == {"head": "stream"}
    assert len(re.findall(r"%head_logits[.\d]* = \S+ custom-call\(",
                          text)) == 1
    table = f"bf16[{vocab},{width}]"
    copies = [line.strip()[:140] for line in text.splitlines()
              if table in line and re.search(r" copy-(start|done)\(", line)]
    assert not copies, copies
    prefetched = [line.strip()[:200] for line in text.splitlines()
                  if "cross_program_prefetch" in line and "wte" in line]
    assert not prefetched, prefetched
    # the parameter itself, not a copy of it in VMEM (memory space 1)
    wte, = re.findall(r"%(params__wte__[.\d]*) = (\S+) parameter\(", text)
    assert "S(1)" not in wte[1]
    readers = [line for line in text.splitlines() if f"%{wte[0]}" in line
               and " parameter(" not in line]
    assert any("/embed/" in line and "gather" in line for line in readers)
    assert any("%head_logits" in line for line in readers)


@pytest.mark.parametrize("program", ["fused", "verify"])
def test_multi_step_programs_move_no_pool(one_chip, no_compile_cache, as_tpu,
                                          program):
    """The K-step fused decode (rounds scanned over the layer scan, the pool
    carried through both) and the verify program (K one-token rows a
    sequence) inherit the property."""
    layers, K = 2, 4
    model = serving_model(64, layers)
    params, pool, tables, starts = serving_avals(one_chip, model, CELL_SEQS)
    if program == "fused":
        def fn(params, pool, toks, tables, starts):
            return model.decode_paged_multi(params, pool, toks, tables,
                                            starts, K)
        toks = aval(one_chip, (CELL_SEQS,), jnp.int32)
    else:
        def fn(params, pool, segs, tables, starts):
            return model.verify_paged_multi(params, pool, segs, tables,
                                            starts)
        toks = aval(one_chip, (CELL_SEQS, K), jnp.int32)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, toks, tables, starts).compile()
    assert_moves_no_pool(compiled, pool, layers)


#: (configuration, traffic) -> ``temp_size_in_bytes`` of the decode round of
#: the configuration cut to two layers, at commit d93b478 (PR 49); PR 52's
#: one-tile form read 3,113,472 and 4,086,272
PARENT_ROUND_TEMPS = {
    ("gigachat3.1-702b-a36b", "serve-longdoc"): 9_792_000,
    ("longcat-flash-chat", "serve-longout"): 29_088_768,
}


@pytest.mark.parametrize("cell", sorted(PARENT_ROUND_TEMPS), ids=lambda c: c[0])
def test_an_expert_models_round_holds_no_row_buffer(one_chip, no_compile_cache,
                                                    as_tpu, cell):
    """The decode round of each latent cell at the cell's own shapes (32 and
    96 one-token rows, every width as published, two layers): it compiles,
    its expert layer sorts no (row, pick) pairs, and without the row buffer
    its temporaries are no larger than they were with it."""
    from benchmark.harness.cell import load_json
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    model_cfg = load_json("configs", cell[0] + ".json")["model"]
    engine = load_json("traffic", cell[1] + ".json")["engine"]
    model = TransformerLM(TransformerConfig(**{**model_cfg, "num_layers": 2}))
    rows = engine["max_seqs"]
    params, pool, tables, starts = serving_avals(
        one_chip, model, rows, engine["num_blocks"], engine["block_size"],
        engine["max_seq_len"] // engine["block_size"])

    def program(params, ids, pool, tables, starts, logit_rows):
        return model.forward_paged(params, ids, pool, tables, starts,
                                   logit_rows=logit_rows, moe_stats=True)

    compiled = jax.jit(program, donate_argnums=(2,)).lower(
        params, aval(one_chip, (rows, 1), jnp.int32), pool, tables, starts,
        aval(one_chip, (rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert sorts and not any(f"[{rows * model.config.moe_top_k}]" in line
                             for line in sorts), sorts
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps <= PARENT_ROUND_TEMPS[cell], temps


def test_a_lightning_layers_round_is_one_call_on_the_models_rows(
        one_chip, no_compile_cache, as_tpu):
    """The decode round of a (``sparse_attn``, ``linear_attn``) model at the
    ``serve-doc16k`` cell's widths and shapes (48 one-token rows, 49 slots):
    ONE ``linear_decode`` call for the lightning layer, which takes q, k and v
    and gives o as ``(rows, heads * head_dim)``: no float32 copy of the rows
    a head a column or a head a row around it (the parent made four a layer:
    ``cols(q)``, ``cols(k)``, v and o as ``f32[48,4,8,128]``)."""
    from benchmark.harness.cell import load_json
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    types = ["sparse_attn", "linear_attn"]
    model = TransformerLM(TransformerConfig(**{
        **load_json("configs", "minicpm-sala.json")["model"],
        "num_layers": len(types), "layer_types": types}))
    engine = load_json("traffic", "serve-doc16k.json")["engine"]
    rows, width = engine["max_seqs"], model.config.hidden_size
    params, pool, tables, starts = serving_avals(
        one_chip, model, rows, engine["num_blocks"], engine["block_size"],
        engine["max_seq_len"] // engine["block_size"])
    state = jax.tree.map(
        lambda a: aval(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_state_cache(
            rows, engine["max_seq_len"], dtype=jnp.bfloat16)))

    def program(params, ids, pool, state, tables, starts, slots, logit_rows):
        return model.forward_paged(
            params, ids, pool, tables, starts, logit_rows=logit_rows,
            moe_stats=True, rows_apart=True, state=state, row_slots=slots)

    rows_i32 = aval(one_chip, (rows,), jnp.int32)
    text = jax.jit(program, donate_argnums=(2, 3)).lower(
        params, aval(one_chip, (rows, 1), jnp.int32), pool, state, tables,
        starts, rows_i32, rows_i32).compile().as_text()
    calls = re.findall(r"%linear_decode[.\d]* = \((\w+\[[\d,]+\])\S*, "
                       r".* custom-call\(", text)
    assert calls == [f"f32[{rows},{width}]"], calls
    relaid = [line.strip()[:120] for line in text.splitlines() if re.search(
        rf"= f32\[{rows},4,(?:128,8|8,128)\]\S* (?:copy|transpose|convert)\(",
        line)]
    assert not relaid, relaid


#: the cells whose layers keep a convolution's window beside their state:
#: (configuration, traffic, the window arrays of the cell's layer groups)
CONV_CELLS = [
    ("ling-3.0-flash", "serve-reason",
     [(1, 129, 3, 12288), (5, 129, 3, 12288)]),
    ("falcon-h1-34b-instruct", "serve-crowd", [(9, 65, 3, 5120)]),
]
_MOVES = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\(?[^=]*?) "
                    r"(?:copy|copy-start|gather|scatter)\((.*)$")


def window_movers(text, shapes):
    """The ``copy``, ``gather`` and ``scatter`` instructions of optimized HLO
    (fused computations included, a copy into another memory space too) whose
    result or one of whose operands is an array of one of ``shapes``."""
    wanted = {"[" + ",".join(map(str, s)) + "]" for s in shapes}
    shape_of = {m.group(1): m.group(2) for m in re.finditer(
        r"%?([\w.\-]+)(?: =|:) \(*\w+(\[[\d,]*\])", text)}
    found = []
    for line in text.splitlines():
        m = _MOVES.match(line)
        if m:
            operands = re.findall(r"%([\w.\-]+)", m.group(2).split("), ")[0])
            moved = set(re.findall(r"\[[\d,]*\]", m.group(1))) | {
                shape_of.get(name) for name in operands}
            if moved & wanted:
                found.append(line.strip()[:140])
    return found


def conv_cell(name, traffic):
    """(model, the engine's geometry) of a cell, every width as published."""
    from benchmark.harness.cell import load_json
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    mix = load_json("traffic", traffic + ".json")
    return TransformerLM(TransformerConfig(**{
        **load_json("configs", name + ".json")["model"],
        **mix.get("model", {})})), mix["engine"]


@pytest.mark.parametrize("cell", CONV_CELLS, ids=lambda c: c[1])
def test_the_convolutions_kernel_at_a_cells_slots(one_chip, no_compile_cache,
                                                  as_tpu, cell):
    """``conv_rows`` alone at the cell's rows, slots and channels (128 rows on
    129 x 3 x 12288 without a bias, 64 on 65 x 3 x 5120 with one), the window
    array donated: Mosaic takes ``conv_decode``, in place on the array as it
    lies (no second array, no gathered copy of the rows' windows)."""
    from deepspeed_tpu.ops.transformer import linear_attention as la

    _, engine = conv_cell(*cell[:2])
    shape, rows = cell[2][-1], engine["max_seqs"]
    ch, biased = shape[3], cell[1] == "serve-crowd"
    assert shape[1] == 1 + rows

    def call(window, layer, slots, x, taps, bias, fresh):
        # by its name where there is a bias: ``conv_rows`` keeps those in XLA
        return (la.conv_decode if biased else la.conv_rows)(
            window, layer, slots, x, taps, bias if biased else None, fresh)

    bf16 = jnp.bfloat16
    compiled = jax.jit(call, donate_argnums=(0,)).lower(
        aval(one_chip, shape, bf16), aval(one_chip, (), jnp.int32),
        aval(one_chip, (rows,), jnp.int32), aval(one_chip, (rows, ch), bf16),
        aval(one_chip, (4, ch), bf16), aval(one_chip, (ch,), bf16),
        aval(one_chip, (rows,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "conv_decode" in text
    assert "input_output_alias" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 * 1024
    planes = (shape[0], shape[2], shape[1], shape[3])
    assert window_movers(text, [shape, planes]) == []


@pytest.mark.parametrize("step", ["round", "mixed"])
@pytest.mark.parametrize("cell", CONV_CELLS, ids=lambda c: c[1])
def test_a_state_layers_window_is_moved_by_conv_decode_alone(
        one_chip, no_compile_cache, as_tpu, cell, step):
    """Both programs of both cells through ``forward_paged`` at the cells' own
    shapes (the decode round: 128 | 64 one-token rows apart; the mixed step:
    those and three tiles of 128 in a 512-row budget). ``serve-reason``: ONE
    ``conv_decode`` call a KDA layer's body, and no ``copy`` (into another
    layout or another memory space), ``gather`` or ``scatter`` reads or
    writes an array of a window array's shape, as it is declared or as the
    planes the kernel takes. The parent's round relaid the whole array into
    its layer scan and back, once each a dispatch (``copy.211`` / ``copy.237
    bf16[5,129,3,12288]``), and gathered and scattered the rows' windows a
    layer; the mixed step's tiles (``conv_tiles``) read and write a slot with
    the rows of its HBM tile where the array lies. ``serve-crowd``: the SSD
    mixer's convolution has a bias and keeps the XLA form, copies and all:
    its layer's body holds the two custom calls the benchmark's own test pins
    (``tests/benchmark/test_chip_compile_falcon.py``)."""
    model, engine = conv_cell(*cell[:2])
    seqs = engine["max_seqs"]
    rows = seqs if step == "round" else engine["token_budget"]
    params, pool, tables, starts = serving_avals(
        one_chip, model, rows, engine["num_blocks"], engine["block_size"],
        engine["max_seq_len"] // engine["block_size"])
    state = jax.tree.map(
        lambda a: aval(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_state_cache(
            seqs, engine["max_seq_len"], dtype=jnp.bfloat16)))
    windows = [group["conv"].shape for group in state.values()]
    assert sorted(windows) == cell[2]

    def program(params, ids, pool, state, tables, starts, slots, logit_rows):
        return model.forward_paged(
            params, ids, pool, tables, starts, logit_rows=logit_rows,
            seg_from=seqs if rows > seqs else None, moe_stats=True,
            rows_apart=rows == seqs, state=state, row_slots=slots)

    rows_i32 = aval(one_chip, (rows,), jnp.int32)
    text = jax.jit(program, donate_argnums=(2, 3)).lower(
        params, aval(one_chip, (rows, 1), jnp.int32), pool, state, tables,
        starts, rows_i32, aval(one_chip, (seqs,), jnp.int32)
    ).compile().as_text()
    calls = re.findall(r"%conv_decode[.\d]* = \((\w+\[[\d,]+\])\S*, "
                       r"(\w+\[[\d,]+\]).* custom-call\(", text)
    planes = [(n, k, s, ch) for n, s, k, ch in windows]
    if cell[1] == "serve-crowd":
        assert calls == [] and window_movers(text, windows)
        return
    assert sorted(calls) == sorted(
        (f"f32[{seqs},{ch}]", f"bf16[{n},{k},{s},{ch}]")
        for n, k, s, ch in planes), calls
    assert window_movers(text, windows + planes) == []


def test_linear_decode_starts_no_copy_for_a_cell_with_no_live_row():
    """The kernel's body as it is traced (nothing compiles): the slot array
    is left where it lies (no block of it is the pipeline's to copy), and
    every copy the kernel starts or waits for sits under a branch taken only
    where the cell's count of live rows is above zero: a cell whose rows are
    all dead, and a dead row of any cell, moves nothing of the slot array,
    the trash slot included."""
    from jax.extend.core import Var

    from deepspeed_tpu.ops.transformer import linear_attention as la

    rows, heads, hd = 48, 32, 128
    sds = jax.ShapeDtypeStruct
    act = sds((rows, heads, hd), jnp.bfloat16)
    # a function of its own: a trace of ``la.linear_decode`` itself would be
    # found again, interpreted, by a later ``jax.jit`` of it in this process
    traced = jax.make_jaxpr(lambda *args: la.linear_decode(*args))(
        sds((6, rows + 1, heads, hd, hd), jnp.float32), sds((), jnp.int32),
        sds((rows,), jnp.int32), act, act, act, sds((rows,), jnp.bool_))

    def inner(eqn):
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield sub

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in inner(eqn):
                yield from eqns(sub)

    [call] = [e for e in eqns(traced.jaxpr) if e.primitive.name == "pallas_call"]
    in_hbm = [str(m.block_aval) for m in call.params["grid_mapping"].block_mappings
              if m.block_aval.shape == (6, rows + 1, heads, hd, hd)]
    assert len(in_hbm) == 2 and all(a.startswith("Ref<any>") for a in in_hbm)
    body = call.params["jaxpr"]
    made_by = {out: e for e in body.eqns for out in e.outvars}

    def ancestors(var):
        eqn = made_by.get(var)
        if eqn is None:
            return set()
        return {eqn.primitive.name}.union(
            *(ancestors(v) for v in eqn.invars if isinstance(v, Var)))

    copies = 0
    for eqn in body.eqns:
        assert not eqn.primitive.name.startswith("dma_"), "a copy on every path"
        held = sum(e.primitive.name.startswith("dma_")
                   for sub in inner(eqn) for e in eqns(sub))
        if held:
            assert eqn.primitive.name == "cond"
            assert "gt" in ancestors(eqn.invars[0]), "not under n_live > 0"
            copies += held
    assert copies >= 4      # the first fetch, the walk's three, the drain


def test_flash_refusal_is_loud(as_tpu, monkeypatch):
    """On a TPU backend a shape the kernel cannot take gives way to the XLA
    path only with a logged reason, and not at all when the caller named the
    kernel. (Nothing compiles here: the refusal comes before any kernel.)"""
    from deepspeed_tpu.ops.transformer.attention import (UnsupportedShape,
                                                         attention,
                                                         xla_attention)

    from deepspeed_tpu.utils.logging import logger

    warned = []
    monkeypatch.setattr(logger, "warning", warned.append)
    q = jnp.ones((1, 100, 2, 64), jnp.float32)  # 100 is no multiple of 128
    out = attention(q, q, q, causal=True)
    assert jnp.allclose(out, xla_attention(q, q, q, causal=True))
    assert len(warned) == 1 and "multiples of 128" in warned[0]
    with pytest.raises(UnsupportedShape):
        attention(q, q, q, causal=True, impl="pallas_flash")
