"""Measure DataLoader input-pipeline throughput: single-process fetch vs
worker processes over the native shared-memory ring queue
(csrc/shm_queue.cpp) — the data_feed/BlockingQueue analog (reference:
framework/data_feed.cc + dataloader_iter.py:358 use_shared_memory path).

Writes benchmarks/DATALOADER_THROUGHPUT.json and prints one JSON line.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

BATCH = 32
IMG = (3, 224, 224)
N_BATCHES = 60


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.io import DataLoader, Dataset

    class Synth(Dataset):
        """CPU-bound sample generation (decode+augment stand-in)."""

        def __len__(self):
            return BATCH * N_BATCHES

        def __getitem__(self, i):
            rng = np.random.default_rng(i)
            img = rng.standard_normal(IMG).astype(np.float32)
            img = (img - img.mean()) / (img.std() + 1e-6)   # "augment"
            return img, np.int64(i % 10)

    bytes_per_batch = BATCH * int(np.prod(IMG)) * 4
    out = {"batch": BATCH, "img": list(IMG), "n_batches": N_BATCHES,
           "mb_per_batch": round(bytes_per_batch / 1e6, 2),
           # worker processes can only beat in-process fetch when there
           # are spare cores to run them on; on a 1-core box the shm hop
           # is pure overhead and the numbers say so honestly
           "host_cores": os.cpu_count()}
    for workers in (0, 2, 4):
        dl = DataLoader(Synth(), batch_size=BATCH, num_workers=workers,
                        use_shared_memory=True)
        dl.shm_slot_size = 64 << 20   # 19.3 MB batches + pickle framing
        # one warm pass compiles/builds the native queue off the clock
        it = iter(dl)
        next(it)
        t0 = time.perf_counter()
        n = 1
        for _ in it:
            n += 1
        dt = time.perf_counter() - t0
        key = f"workers_{workers}"
        out[key] = {
            "batches_per_sec": round((n - 1) / dt, 2),
            "MBps": round((n - 1) * bytes_per_batch / dt / 1e6, 1),
        }
    path = os.path.join(os.path.dirname(__file__),
                        "DATALOADER_THROUGHPUT.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
