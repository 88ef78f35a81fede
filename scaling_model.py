"""Communication-volume scaling model (BASELINE.md row 3: "ZeRO scaling
efficiency 8→256 measured" — measurable here as HLO-derived comm volume on
the 8-device virtual mesh, projected to 64/256 chips).

For each tracked parallelism config, the engine's fused train step is compiled
on an 8-device mesh and its HLO is scanned for collectives. Per-chip wire
bytes follow the standard ring formulas:

    all-reduce          2·S·(n-1)/n      (S = tensor bytes)
    all-gather          S_out·(n-1)/n
    reduce-scatter      S_in·(n-1)/n
    all-to-all          S·(n-1)/n
    collective-permute  S

ZeRO's collective operands are full-parameter/gradient sized independent of n,
so S_global is recovered from the n=8 measurement and re-evaluated at the
target scale. The efficiency projection assumes v5e ICI ≈ 90 GB/s usable
per chip per direction and ZERO compute/comm overlap (worst case — XLA
overlaps in practice), with compute time from the measured headline MFU.

``python scaling_model.py`` writes SCALING_MODEL.json.
"""

import json
import os
import re
import sys

import numpy as np

from bench import PEAK_BF16_FLOPS

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "s16": 2,
               "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def parse_collectives(hlo: str, n_devices: int = 8):
    """Sum OUTPUT bytes per (collective kind, replica-group size) from an HLO
    text dump. The model is profiled with scan_layers=False so per-layer
    collectives appear once per layer in the text (a lax.scan would hide
    L-1 of every in-loop collective from a static count)."""
    totals = {}
    counts = {}
    op_pat = re.compile(r"=\s+(.*?)\s(" + "|".join(COLLECTIVES)
                        + r")(?:-start|-done)?\(")
    shape_pat = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
    for line in hlo.splitlines():
        m = op_pat.search(line)
        if not m:
            continue
        result_types, kind = m.group(1), m.group(2)
        if "-done(" in line:  # async pair: count only the -start
            continue
        # XLA COMBINES collectives: the result may be a tuple of many
        # tensors — sum every element's bytes, not just the first
        size = 0
        for dt, dims in shape_pat.findall(result_types):
            if dt not in DTYPE_BYTES:
                continue
            s = DTYPE_BYTES[dt]
            if dims:
                s *= int(np.prod([int(d) for d in dims.split(",")]))
            size += s
        if size == 0:
            continue
        gm = re.search(r"replica_groups=\{\{([^}]*)\}", line)
        if gm:
            gs = len(gm.group(1).split(","))
        else:
            gm = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
            gs = int(gm.group(2)) if gm else n_devices
        key = (kind, gs)
        totals[key] = totals.get(key, 0) + size
        counts[key] = counts.get(key, 0) + 1
    return totals, counts


def wire_bytes_per_chip(totals, n, dp0, n0=8):
    """Apply the ring formulas per (kind, group size). Groups spanning the
    data(×hpz) axes grow with the chip count (dp_target = dp0 · n/n0);
    model/seq/fixed-size groups (tensor parallel etc.) keep their size."""
    w = 0.0
    for (kind, gs0), s in totals.items():
        gs = gs0 * n // n0 if gs0 == dp0 else gs0
        gs = max(gs, 1)
        if kind == "all-reduce":
            w += 2 * s * (gs - 1) / gs
        elif kind == "all-gather":
            w += s * (gs - 1) / gs           # output is the group-global tensor
        elif kind == "reduce-scatter":
            w += s * gs0 * (gs - 1) / gs     # output is the shard: global = s*gs0
        elif kind == "all-to-all":
            w += s * (gs - 1) / gs
        else:  # collective-permute
            w += s
    return w


def profile_config(name, ds_config, model_kw, micro_bs=2, seq=128):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm import topology as topo_mod
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    topo_mod.reset_topology()
    cfg = gpt2_config("125m", max_seq_len=seq, scan_layers=False,
                      **model_kw)
    model = TransformerLM(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config)
    topo = topo_mod.get_topology()
    dp = topo.get_dim("data") * topo.get_dim("hpz")
    B = micro_bs * dp
    rng = np.random.default_rng(0)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, seq), dtype=np.int32))}
    batch = engine._shard_batch(batch)
    args = (engine.params,
            engine.master_params if engine._mixed else None,
            engine.opt_state, engine.scaler_state, batch,
            jnp.asarray(0, jnp.int32), jnp.asarray(1e-4, jnp.float32))
    hlo = engine._fused_step_fn.lower(*args).compile().as_text()
    totals, counts = parse_collectives(hlo, n_devices=8)
    dp0 = topo.get_dim("data") * topo.get_dim("hpz")
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(engine.params))
    row = {
        "config": name,
        "mesh": {k: topo.get_dim(k) for k in ("data", "model", "pipe",
                                              "seq", "hpz")},
        "n_params": n_params,
        "hlo_collective_output_bytes_n8": {
            f"{k}@group{g}": v for (k, g), v in sorted(totals.items())},
        "hlo_collective_counts": {
            f"{k}@group{g}": v for (k, g), v in sorted(counts.items())},
    }
    # projection + worst-case efficiency estimate
    ici_bytes_per_s = 90e9  # v5e ICI usable per chip per direction (assumed)
    tokens_per_chip = 8192  # headline-config scale (8 x 1024), not the
    # toy profiling batch: comm volume is batch-independent, compute is not
    flops_step = 6 * n_params * tokens_per_chip
    # v5e peak from the one table (bench.py), at the old headline MFU ~0.5
    t_compute = flops_step / (PEAK_BF16_FLOPS["TPU v5e"] * 0.5)
    for n in (8, 64, 256):
        wire = wire_bytes_per_chip(totals, n, dp0)
        t_comm = wire / ici_bytes_per_s
        row[f"n{n}"] = {
            "wire_bytes_per_chip": int(wire),
            "projected_efficiency_no_overlap": round(
                t_compute / (t_compute + t_comm), 4),
        }
    return row


def main():
    configs = [
        ("zero1_dp8", {"zero_optimization": {"stage": 1}, "mesh": {"data": 8}},
         {}),
        ("zero2_dp8", {"zero_optimization": {"stage": 2}, "mesh": {"data": 8}},
         {}),
        ("zero3_dp8", {"zero_optimization": {
            "stage": 3, "stage3_param_persistence_threshold": 0},
            "mesh": {"data": 8}}, {}),
        ("zero3_dp4_tp2", {"zero_optimization": {
            "stage": 3, "stage3_param_persistence_threshold": 0},
            "mesh": {"data": 4, "model": 2}}, {}),
        ("zero3_hpz_dp4x2", {"zero_optimization": {
            "stage": 3, "stage3_param_persistence_threshold": 0,
            "zero_hpz_partition_size": 2},
            "mesh": {"data": 8}}, {}),
    ]
    base = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }
    rows = []
    for name, over, model_kw in configs:
        ds = {**base, **over}
        try:
            row = profile_config(name, ds, model_kw)
        except Exception as e:  # record, keep profiling
            row = {"config": name, "error": f"{type(e).__name__}: {e}"[:300]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = {
        "method": "HLO (unrolled layers) of the compiled fused train step on the 8-device "
                  "virtual mesh; per-chip wire bytes via ring-collective "
                  "formulas per replica-group size (data-axis groups grow with n, model-axis groups stay fixed); S_global recovered from n=8 operand sizes "
                  "(ZeRO collectives are full-model-sized, n-independent); "
                  "efficiency projection assumes 90 GB/s usable ICI per "
                  "chip and zero compute/comm overlap (worst case)",
        "model": "gpt2-125m geometry, seq 128, micro_batch 2/chip for the HLO; efficiency projected at 8192 tokens/chip/step (headline scale)",
        "configs": rows,
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "SCALING_MODEL.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    # must run on the virtual CPU mesh (pin before any backend use)
    from deepspeed_tpu.utils.xla_env import force_device_count_flags

    os.environ["XLA_FLAGS"] = force_device_count_flags(
        os.environ.get("XLA_FLAGS", ""), 8)
    import jax

    jax.config.update("jax_platforms", "cpu")
    import logging

    logging.getLogger("DeepSpeedTPU").setLevel(logging.WARNING)
    sys.exit(main())
