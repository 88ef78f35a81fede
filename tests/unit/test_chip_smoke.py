"""CPU rehearsal of ``chip_smoke.py`` (on-chip-measurement guide, section 2,
rehearsals 1 and 2): its phase functions at a tiny size on the virtual CPU
mesh, the script's refusal of anything but a TPU, and the two properties the
chip machine depends on: no import initialises a backend (a chip belongs to
one process), and the compile cache goes where the environment says.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.models import gpt2_config  # noqa: E402

TINY = dict(hidden_size=128, num_layers=2, num_heads=2, vocab_size=512)


def _child(code, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO, **env}
    full = {k: v for k, v in full.items() if v is not None}
    return subprocess.run([sys.executable, "-c", code], env=full, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def _train_cfg():
    return gpt2_config("350m", max_seq_len=128, remat=True,
                       remat_policy="dots", scan_layers=False, **TINY)


def test_train_phase_rehearsal():
    n = len(jax.devices())
    out = chip_smoke.train_phase(_train_cfg(), seq=128, micro_bs=2, steps=4,
                                 mesh={"data": n}, expect_kernel=False)
    assert len(out["losses"]) == 4 and out["losses"][-1] < out["losses"][0]
    assert out["tpu_custom_call"] is False  # XLA attention on the CPU


def test_serve_phase_rehearsal():
    out = chip_smoke.serve_phase(
        gpt2_config("350m", max_seq_len=256, **TINY),
        prompt_lens=(70, 40, 90, 33), new_tokens=(12, 9, 6, 10), max_seqs=4,
        max_seq_len=256, token_budget=32, prefill_chunk=16,
        expect_kernel=False)
    assert out["requests"] == 4 and out["tokens_served"] == 37
    assert out["mixed_steps"] >= 1
    # float32-exact on the CPU: the engine serves what the plain forward says
    assert out["ref_exact"] == 1.0


def test_zero3_phase_rehearsal():
    """The four-chip phase on the virtual mesh: shards on every device,
    losses in agreement with stage 0. (The CPU reports no peak bytes and
    writes its reduce-scatter as all-reduce + slice.)"""
    n = len(jax.devices())
    out = chip_smoke.zero3_phase(
        _train_cfg(), seq=128, micro_bs=2, steps=3, n_devices=n,
        expect_kernel=False, expect_peak_drop=False,
        expect_reduce_scatter=False)
    z3 = out["zero3"]
    assert z3["collectives"]["all-gather"] > 0
    sharded = {name for name, _ in z3["sharded"]}
    assert {"params['blocks']['w_up']", "master['blocks']['w_up']",
            "adam_m['wte']", "adam_v['wte']"} <= sharded
    assert all(name.startswith("params[") or "mlp_up_bias" in name
               for name, _ in z3["replicated"]), z3["replicated"]


@pytest.mark.parametrize("mesh", [{"data": 8},
                                  {"data": 2, "seq": 2, "model": 2}], ids=str)
def test_flash_kernel_maps_itself_over_the_mesh(mesh):
    """XLA cannot partition a Mosaic kernel, so under the engine's mesh the
    flash kernel wraps itself in ``shard_map`` (batch over the DP axes, heads
    over seq x model). Interpreted here; same losses as XLA attention."""
    from deepspeed_tpu.ops.transformer.attention import set_default_impl

    cfg = gpt2_config("350m", max_seq_len=128, remat=True, remat_policy="dots",
                      scan_layers=False, **{**TINY, "hidden_size": 256,
                                            "num_heads": 4})  # head size 64
    losses = {}
    for impl in ("xla", "pallas_flash"):
        set_default_impl(impl)
        try:
            losses[impl] = chip_smoke.train_phase(
                cfg, seq=128, micro_bs=2, steps=3, zero_stage=3, mesh=mesh,
                expect_kernel=False)["losses"]
        finally:
            set_default_impl(None)
    assert losses["pallas_flash"] == pytest.approx(losses["xla"], rel=2e-3)


@pytest.mark.parametrize("phase", ["train_phase", "serve_phase"])
def test_a_failed_phase_fails_the_run(phase, monkeypatch, capsys, tmp_path):
    """Phases run unguarded: an exception out of either one leaves ``main``
    (a non-zero exit with a traceback) before the result line is printed."""
    from deepspeed_tpu.utils import xla_env

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda n: device)
    monkeypatch.setattr(xla_env, "enable_compile_cache", lambda: str(tmp_path))
    canned = {"losses": [2.0, 1.0], "tpu_custom_call": True, "compile_s": 0.0,
              "first_step_s": 0.0, "steady_step_s": 0.0, "peak_bytes": None}
    monkeypatch.setattr(chip_smoke, "train_phase", lambda *a, **k: canned)

    def boom(*a, **k):
        raise RuntimeError("injected")

    monkeypatch.setattr(chip_smoke, phase, boom)
    with pytest.raises(RuntimeError, match="injected"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_script_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_imports_initialise_no_backend():
    """``chiprun`` gives the chip to one process: a parent that only imports
    must not take it from the child it starts."""
    proc = _child(
        "import deepspeed_tpu, deepspeed_tpu.serve, deepspeed_tpu.inference.v2\n"
        "import chip_smoke\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('clean')")
    assert proc.returncode == 0 and "clean" in proc.stdout, proc.stderr[-2000:]


@pytest.mark.parametrize("placed", [False, True])
def test_compile_cache_placement(placed, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code and JAX uses
    that directory. Unset: one fixed path inside the checkout."""
    proc = _child(
        "import json, jax\n"
        "from deepspeed_tpu.utils import xla_env\n"
        "seen = []\n"
        "update = jax.config.update\n"
        "jax.config.update = lambda k, v: (seen.append(k), update(k, v))\n"
        "path = xla_env.enable_compile_cache()\n"
        "print(json.dumps({'path': path, 'set_in_code': seen,\n"
        "                  'jax': jax.config.jax_compilation_cache_dir,\n"
        "                  'min_s': jax.config."
        "jax_persistent_cache_min_compile_time_secs}))",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path) if placed else None)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if placed else os.path.join(
        REPO, ".dstpu_build", "jax_cache")
    assert got["path"] == got["jax"] == want
    assert ("jax_compilation_cache_dir" in got["set_in_code"]) is (not placed)
    assert got["min_s"] == 0
