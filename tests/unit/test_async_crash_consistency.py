"""Async-checkpoint crash-consistency INTEGRATION test (VERDICT r4 next #6):
a REAL child process training with ``checkpoint.async_save`` is SIGKILLed
mid-GAS immediately after an async save window — while the writer thread may
still be draining — then restarted. ``latest`` must resolve to a COMPLETE
checkpoint (every file of the tag loadable) and the loss curve must continue
(reference behavior contract: ``runtime/checkpoint_engine/`` +
``engine.load_checkpoint:2710``; the tmp→replace + pointer-rides-the-queue
design in ``async_checkpoint_engine.py``)."""

import json
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WORKER = textwrap.dedent("""
    import json, os, signal, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, {repo!r})
    import numpy as np
    import deepspeed_tpu
    from tests.unit.simple_model import make_simple_model, random_batch

    work = os.environ["CRASH_TEST_DIR"]
    incarnation = int(os.environ["CRASH_INCARNATION"])
    ckpt = os.path.join(work, "ckpt")
    gas = 2
    engine, *_ = deepspeed_tpu.initialize(
        model=make_simple_model(16), config={{
            "train_micro_batch_size_per_gpu": 4,
            "gradient_accumulation_steps": gas,
            "optimizer": {{"type": "Adam", "params": {{"lr": 1e-2}}}},
            "zero_optimization": {{"stage": 1}},
            "checkpoint": {{"async_save": True}},
            "steps_per_print": 0,
            "mesh": {{"data": 2}},
        }})
    resumed_step = None
    if os.path.exists(os.path.join(ckpt, "latest")):
        engine.load_checkpoint(ckpt)
        resumed_step = engine.global_steps
    total_steps = 6

    def micro(step, m):
        batch = random_batch(batch_size=8, hidden_dim=16, seed=step * 7 + m)
        loss = engine(batch)
        engine.backward(loss)
        return loss

    start = engine.global_steps
    for step in range(start, total_steps):
        losses = [micro(step, m) for m in range(gas)]
        engine.step()
        loss = float(losses[-1])
        engine.save_checkpoint(ckpt, tag=f"step{{engine.global_steps}}")
        with open(os.path.join(work, "progress.jsonl"), "a") as f:
            f.write(json.dumps({{"inc": incarnation, "resumed": resumed_step,
                                 "step": engine.global_steps,
                                 "loss": loss}}) + "\\n")
        if incarnation == 0 and engine.global_steps == 3:
            # the async save of step3 was ENQUEUED above (save_checkpoint
            # returns before the writer drains). Run half of the next GAS
            # window so we die genuinely mid-accumulation, then SIGKILL —
            # no atexit, no drain.
            micro(step + 1, 0)
            os.kill(os.getpid(), signal.SIGKILL)
    sys.exit(0)
""")


def _run_worker(tmp_path, incarnation):
    env = dict(os.environ)
    env["CRASH_TEST_DIR"] = str(tmp_path)
    env["CRASH_INCARNATION"] = str(incarnation)
    env["PYTHONPATH"] = REPO
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER.format(repo=REPO))
    return subprocess.run([sys.executable, str(worker)], env=env,
                          timeout=300, capture_output=True, text=True)


def test_sigkill_mid_gas_then_resume(tmp_path):
    p0 = _run_worker(tmp_path, 0)
    # the first incarnation must have died by SIGKILL, not finished
    assert p0.returncode == -signal.SIGKILL, (p0.returncode, p0.stderr[-800:])

    ckpt = tmp_path / "ckpt"
    latest = (ckpt / "latest").read_text().strip()
    # whatever tag latest points at must be COMPLETE: every npz of the tag
    # parses (tmp→replace guarantees no torn file shadows a complete one)
    tag_dir = ckpt / latest
    assert tag_dir.is_dir(), f"latest -> {latest} but no such tag dir"
    files = list(tag_dir.glob("*.ckpt"))
    assert files, f"latest tag {latest} has no checkpoint files"
    for f in files:  # every file of the tag parses as a complete npz archive
        with np.load(f, allow_pickle=False) as z:
            assert len(z.files) > 0, f"{f} is an empty archive"

    p1 = _run_worker(tmp_path, 1)
    assert p1.returncode == 0, p1.stderr[-1500:]

    lines = [json.loads(x) for x in
             (tmp_path / "progress.jsonl").read_text().splitlines()]
    first = [x for x in lines if x["inc"] == 0]
    second = [x for x in lines if x["inc"] == 1]
    assert first[-1]["step"] == 3
    # resume landed on a step the async engine had durably committed: at
    # least the step BEFORE the kill-window save (its write may or may not
    # have drained), never past the kill point
    assert second and second[0]["resumed"] in (2, 3), second[0]
    assert second[-1]["step"] == 6
    # the loss curve continues: every loss finite, and no step is re-done or
    # skipped — the resumed incarnation's steps pick up exactly past the
    # checkpoint it loaded (each batch is fresh data, so monotonic-decrease
    # is not the contract; continuity is)
    assert all(np.isfinite(x["loss"]) for x in lines)
    steps_seen = [x["step"] for x in second]
    assert steps_seen == list(range(second[0]["resumed"] + 1, 7)), steps_seen
