"""Decode-bound DataLoader scaling benchmark (VERDICT round-1 item 6).

Builds an on-disk JPEG dataset and times epochs at several num_workers
settings. On a multi-core host the worker-process path scales with cores
(JPEG decode is GIL-bound Python/PIL work); on a single-core machine — like
this build's CI — workers can only add IPC overhead, so interpret results
accordingly (`nproc` is printed first).

Usage: python tools/bench_dataloader.py [num_images] [height width]
"""
from __future__ import annotations

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


class JpegDS:
    """Module-level (hence picklable) so the DataLoader's host-purity probe
    admits real worker processes — a locally-defined class silently demoted
    the benchmark to the threaded fallback it exists to compare against."""

    def __init__(self, paths):
        self.paths = paths

    def __len__(self):
        return len(self.paths)

    def __getitem__(self, i):
        from PIL import Image

        img = np.asarray(Image.open(self.paths[i]).convert("RGB"))
        img = img[8:8 + 224, 8:8 + 224]
        if i % 2:
            img = img[:, ::-1]
        return (np.ascontiguousarray(img.transpose(2, 0, 1),
                                     dtype=np.float32),
                np.float32(i % 10))


def main():
    from PIL import Image

    from mxnet_tpu.gluon.data import DataLoader

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    h, w = (int(sys.argv[2]), int(sys.argv[3])) if len(sys.argv) > 3 \
        else (480, 640)

    print("cores:", os.cpu_count())
    tmp = tempfile.mkdtemp(prefix="mxtpu_dlbench_")
    rng = np.random.RandomState(0)
    paths = []
    for i in range(n):
        arr = rng.randint(0, 255, (h, w, 3), np.uint8)
        p = os.path.join(tmp, "i%d.jpg" % i)
        Image.fromarray(arr).save(p, quality=90)
        paths.append(p)

    for nw in (0, 2, 4, 8):
        dl = DataLoader(JpegDS(paths), batch_size=32, num_workers=nw)
        list(dl)  # warm: pool spin-up + page cache
        t0 = time.perf_counter()
        batches = sum(1 for _ in dl)
        dt = time.perf_counter() - t0
        print("num_workers=%d: %.2fs  %.0f imgs/s  (%d batches)"
              % (nw, dt, n / dt, batches))


if __name__ == "__main__":
    main()
