"""TPU accelerator (the production backend).

Fills the role ``cuda_accelerator.py`` plays in the reference: the concrete
accelerator every subsystem talks to through ``get_accelerator()``.
"""

import functools

from .abstract_accelerator import DeepSpeedAccelerator

# per-chip HBM for runtimes that don't expose memory_stats(); live stats win
# when present. A device kind that is in neither is an error, not 16 GB
_HBM_TABLE = {
    "TPU v4": 32e9,
    "TPU v5 lite": 16e9,
    "TPU v5e": 16e9,
    "TPU v5p": 95e9,
    "TPU v6 lite": 32e9,
    "TPU v6e": 32e9,
}


class TPU_Accelerator(DeepSpeedAccelerator):
    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"

    def is_synchronized_device(self) -> bool:
        return False

    @functools.lru_cache(None)
    def _local_devices(self):
        import jax

        devs = [d for d in jax.local_devices()]
        return devs

    def devices(self):
        return self._local_devices()

    def global_device_count(self) -> int:
        import jax

        return jax.device_count()

    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        # fp16 compute is supported but bf16 is native on the MXU
        return True

    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    # ------------------------- device properties -------------------------
    def device_kind(self, device_index=0) -> str:
        return self.devices()[device_index].device_kind

    def total_memory(self, device_index=0) -> int:
        """Per-chip HBM: live runtime stats when available, else the known
        per-generation table (the seam the autotuner asks instead of keeping
        its own hardware knowledge)."""
        live = super().total_memory(device_index)
        if live:
            return live
        kind = self.device_kind(device_index)
        if kind not in _HBM_TABLE:
            raise KeyError(
                f"device_kind {kind!r} reports no memory_stats() and is not "
                "in tpu_accelerator._HBM_TABLE: add it with its source")
        return int(_HBM_TABLE[kind])

    def memory_stats(self, device_index=0) -> dict:
        return self._stats(device_index)
