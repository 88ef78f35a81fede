"""Test harness configuration.

The reference's distributed test harness (``tests/unit/common.py:113 DistributedExec``)
forks N processes with fake ranks over gloo/nccl. The TPU-native equivalent (per
SURVEY.md §4) is a deterministic virtual device mesh: 8 CPU devices via
``--xla_force_host_platform_device_count``, so every test runs real XLA collectives
single-process. Env vars must be set before the first jax import.
"""

import importlib.util as _ilu
import os

# load xla_env by FILE PATH — importing it through the package would pull in
# deepspeed_tpu/__init__ (and jax) before XLA_FLAGS is set
_spec = _ilu.spec_from_file_location(
    "_xla_env", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "deepspeed_tpu", "utils", "xla_env.py"))
_xla_env = _ilu.module_from_spec(_spec)
_spec.loader.exec_module(_xla_env)

# sequential thunk scheduler + raised collective timeouts: the concurrent
# scheduler reorders independent collectives differently per device →
# intermittent rendezvous deadlocks; the 40 s default termination also fires
# spuriously under heavy programs on 1 vCPU (see VIRTUAL_MESH_STABILITY_FLAGS)
os.environ["XLA_FLAGS"] = _xla_env.virtual_mesh_flags(
    os.environ.get("XLA_FLAGS", ""), 8)
os.environ.setdefault("DS_ACCELERATOR", "cpu")

import jax  # noqa: E402

# tests always run on the virtual CPU mesh, whatever JAX_PLATFORMS says
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Suite tiers (the reference runs `pytest --forked -n 4 unit/` then
# `-m sequential`):
# - `pytest -m smoke`        : fast, compile-light — well under 90 s
# - `pytest -m core`         : distributed-math mid-tier — ~5 min
# - `pytest tests/unit -q`   : full serial (~25-30 min; shard_map compiles)
# - `pytest tests/unit -q -n <N> --dist loadfile` : xdist-parallel — verified;
#   loadfile keeps each FILE on one worker so the per-process topology
#   singleton and the fixed rendezvous port in test_two_process stay safe.
#   (On multi-core CI this is the way to run the full suite in one sitting;
#   this dev host exposes 1 vCPU, where parallel workers cannot help.)
_SMOKE = (
    "test_config.py",
    "test_comm.py::test_launcher",
    "test_comm.py::test_rank_env",
    "test_comm.py::TestMultinodeRunners",
    "test_comm.py::TestTopology",
    "test_inference_v2.py::TestStateManager",
    "test_inference_v2.py::TestPagedKV::test_block_allocator_lifecycle",
    "test_prefix_cache.py::TestBlockManagerInvariants",
    "test_prefix_cache.py::test_shared_prefix_serve_smoke",
    "test_offload.py::TestSplit",
    "test_zero_init_utils.py",
    "test_aio.py",
    "test_diffusion.py",
    "test_aux.py::TestCorpusScaleDataPipeline::test_sampler_resumes_mid_epoch",
    "test_aux.py::test_sampler_reiterates_full_epochs",
)


# `-m core` mid-tier (~4-5 min on this 1-vCPU host): the distributed-math
# essentials — ZeRO-1/2/3 trajectory parity, GAS, bf16, pipeline train, MoE
# EP parity, ZeRO++ qwZ/qgZ, sequence parallel — so regressions in the
# sharded paths surface without the ~30 min full tier (VERDICT r3 weak #3)
_CORE = (
    "test_engine.py::test_zero_stages_match_stage0",
    "test_engine.py::test_zero3_params_actually_sharded",
    "test_engine.py::test_gradient_accumulation",
    "test_engine.py::test_bf16_training",
    "test_engine.py::test_lazy_loss_matches_eager_trajectory",
    "test_pipe.py::TestSpmdPipeline::test_matches_dense_loss_and_grads",
    "test_pipe.py::TestPipelineEngine::test_train_batch_loss_decreases",
    "test_moe.py::TestMoELayer::test_expert_parallel_matches_single_device",
    "test_zeropp.py::TestQwZ::test_qwz_loss_close_to_unquantized_and_trains",
    "test_zeropp.py::TestQgZ::test_reduce_tree_matches_pmean",
    "test_sequence.py::TestUlysses::test_matches_local_attention",
)


# The files that take longest (100 s and more each of the tier-1 command's
# ~5,500 CPU-seconds, measured under 6 workers at PR 47), longest first.
# `--dist loadfile` hands a worker its next file in collection order, so in
# alphabetical order test_zero_sharded.py (300 s) or test_train_resilience.py
# (180 s) can start when the others are nearly done, and five workers wait
# for one: these start first and the short files fill in behind them. Order
# within a file is untouched.
_LONGEST_FIRST = (
    "tests/unit/test_paged_kernel.py",
    "tests/benchmark/test_cells.py",
    "tests/unit/test_mla_decode_live_context.py",
    "tests/unit/test_zero_sharded.py",
    "tests/unit/test_pipelined_dispatch.py",
    "tests/unit/test_train_resilience.py",
    "tests/unit/test_aux.py",
    "tests/benchmark/test_deepseek_v3.py",
    "tests/unit/test_disagg.py",
    "tests/unit/test_speculation.py",
    "tests/unit/test_chip_smoke.py",
    "tests/unit/test_served_weight_reads.py",
    "tests/benchmark/test_longcat_flash.py",
    "tests/unit/test_pool.py",
    "tests/benchmark/test_minicpm_sala.py",
    "tests/benchmark/test_afmoe.py",
    "tests/benchmark/test_falcon_h1.py",
    "tests/benchmark/test_bailing_hybrid.py",
    "tests/benchmark/test_ouro.py",
    "tests/unit/test_extras.py",
    "tests/unit/test_flash_layout.py",
    "tests/unit/test_pipe.py",
    "tests/unit/test_minicpm_sala.py",
)


def _file_rank(item):
    path = item.nodeid.split("::", 1)[0]
    for rank, name in enumerate(_LONGEST_FIRST):
        if path.endswith(name):
            return rank
    return len(_LONGEST_FIRST)


#: a fault of the rehearsed arrival that holds one count of cells: a second
#: four-chip cell "of 7" (six cells and the arrival). With the seventh cell
#: (PR 62) the arrival makes eight, of which two may take four chips, so the
#: case cannot fail the rule it names. ``tests/benchmark/`` is the benchmark's
#: and a ``model_config`` PR edits no file there: the rule is held at any
#: count by ``tests/benchmark/test_arrival_quarter.py`` (new), and a
#: ``benchmark`` PR rewrites the case and takes this line away
_COUNT_PINNED = ("test_arrival.py::test_a_rule_refuses"
                 "[a-second-four-chip-cell-at-seven-cells]")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid.endswith(_COUNT_PINNED):
            item.add_marker(pytest.mark.xfail(
                reason="holds seven cells; see test_arrival_quarter.py"))
        if any(pat in item.nodeid for pat in _SMOKE):
            item.add_marker(pytest.mark.smoke)
        if any(pat in item.nodeid for pat in _CORE):
            item.add_marker(pytest.mark.core)
    items.sort(key=_file_rank)      # stable: the rest keep their order


# Serving/inference test modules run under the runtime sanitizer
# (docs/ANALYSIS.md "checked mode"): the engine builds the self-verifying
# KV cache, every Request.state transition is validated, and scheduler
# close() runs the pool-leak check — so tier-1 exercises the mechanized
# invariants on every real workload these suites drive, not just on the
# seeded-bug tests. An explicit DSTPU_SANITIZE in the environment (e.g.
# DSTPU_SANITIZE=0 to bisect a sanitizer-only failure) wins.
_SANITIZE_FILES = (
    "test_serve.py",
    "test_resilience.py",
    "test_fused_decode.py",
    "test_pipelined_dispatch.py",
    "test_speculation.py",
    "test_inference_v2.py",
    "test_prefix_cache.py",
    "test_chunked_prefill.py",
    "test_recovery.py",
    "test_recovery_soak.py",
    "test_train_resilience.py",
    "test_train_chaos_soak.py",
    "test_pool.py",
    "test_pool_health.py",
    "test_pool_restore.py",
    "test_tenancy.py",
    "test_elastic_pool.py",
    "test_journal_durability.py",
    "test_kv_tier.py",
    "test_zero_sharded.py",
    "test_transfer_engine.py",
    "test_block_classes.py",
)


@pytest.fixture(autouse=True)
def _sanitize_serving_modules(request):
    fspath = str(getattr(request.node, "fspath", ""))
    if (os.path.basename(fspath) in _SANITIZE_FILES
            and "DSTPU_SANITIZE" not in os.environ):
        os.environ["DSTPU_SANITIZE"] = "1"
        try:
            yield
        finally:
            os.environ.pop("DSTPU_SANITIZE", None)
    else:
        yield


# modules that run with the compiled-program audit armed (DSTPU_AUDIT=1,
# docs/ANALYSIS.md "Program audit"): every program these suites compile is
# retraced once per dispatch signature, fingerprinted, and checked against
# the pinned analysis/programs.json — an unpinned program, a digest drift,
# a host callback, or an extra trace fails the test with the registration
# site's file:line. An explicit DSTPU_AUDIT in the environment (e.g.
# DSTPU_AUDIT=0 to bisect, DSTPU_AUDIT=write to re-pin) wins.
_AUDIT_FILES = (
    "test_retrace_guard.py",
    "test_inference_v2.py",
    "test_fused_decode.py",
    "test_speculation.py",
    "test_sampling.py",
    "test_kv_tier.py",
    "test_prefix_cache.py",
    "test_chunked_prefill.py",
    "test_serve.py",
    "test_engine.py",
)


@pytest.fixture(autouse=True)
def _audit_compiled_programs(request):
    fspath = str(getattr(request.node, "fspath", ""))
    if (os.path.basename(fspath) in _AUDIT_FILES
            and "DSTPU_AUDIT" not in os.environ):
        os.environ["DSTPU_AUDIT"] = "1"
        try:
            yield
        finally:
            os.environ.pop("DSTPU_AUDIT", None)
    else:
        yield


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Each test gets a fresh topology (mesh) — mirrors per-test process groups."""
    yield
    from deepspeed_tpu.comm import topology

    topology.reset_topology()
