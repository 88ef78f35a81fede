"""AIO performance sweep (reference ``csrc/aio/py_test/aio_bench_perf_sweep.py``).

Sweeps queue depth (worker threads) × block size for read and write of a
sizeable file and reports MB/s per configuration, with O_DIRECT engagement
stats. Usage: ``python tests/perf/aio_sweep.py [dir] [size_mb]``.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np



# transfer discipline: SIGTERM drains in-flight device work instead of dying
# mid-transfer (see deepspeed_tpu/utils/transfer.py)
from deepspeed_tpu.utils.transfer import install_transfer_guard

install_transfer_guard()

def sweep(path_dir: str, size_mb: int = 256):
    from deepspeed_tpu.ops.aio.py_aio import AsyncIOHandle

    n = size_mb << 20
    data = np.random.default_rng(0).integers(0, 255, n, dtype=np.uint8)
    path = os.path.join(path_dir, "aio_sweep.bin")
    rows = []
    for qd in (1, 2, 4, 8):
        for bs in (1 << 20, 8 << 20):
            for direct in (False, True):
                h = AsyncIOHandle(num_threads=qd, use_direct=direct,
                                  block_size=bs)
                t0 = time.perf_counter()
                rid = h.pwrite(path, data)
                assert h.wait(rid) == 0
                tw = time.perf_counter() - t0
                buf = np.empty_like(data)
                t0 = time.perf_counter()
                rid = h.pread(path, buf)
                assert h.wait(rid) == 0
                tr = time.perf_counter() - t0
                assert np.array_equal(buf, data)
                st = h.stats()
                h.close()
                rows.append({
                    "queue_depth": qd, "block_mb": bs >> 20,
                    "o_direct": direct,
                    "write_MBps": round(size_mb / tw, 1),
                    "read_MBps": round(size_mb / tr, 1),
                    **st,
                })
                print(json.dumps(rows[-1]), flush=True)
    os.unlink(path)
    return rows


if __name__ == "__main__":
    d = sys.argv[1] if len(sys.argv) > 1 else tempfile.gettempdir()
    mb = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    sweep(d, mb)
