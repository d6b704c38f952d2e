"""AIO engine throughput floor (VERDICT r2 weak #6 / item 9).

Absolute GB/s depends on the host's storage (the reference's DeepNVMe
numbers are 7/4 GB/s), so CI asserts a RELATIVE floor:
the thread-pooled chunk-parallel engine must reach a healthy fraction of
raw single-stream file IO on the same mount.  A serializing regression in
the native pool (the failure mode that would justify an io_uring backend)
trips this immediately.
"""
import os
import time

import numpy as np
import pytest

from deepspeed_tpu.io.bench import _sync_and_evict, bench_point

SIZE = 64 << 20


def _raw_gbps(directory: str) -> tuple:
    """Single-stream plain write+fsync / evict / read on the mount."""
    path = os.path.join(directory, f"raw_probe_{os.getpid()}.bin")
    buf = np.random.default_rng(0).integers(0, 255, SIZE, np.uint8)
    try:
        t0 = time.perf_counter()
        with open(path, "wb") as f:
            f.write(buf.tobytes())
            f.flush()
            os.fsync(f.fileno())
        wt = time.perf_counter() - t0
        _sync_and_evict(path)
        t0 = time.perf_counter()
        with open(path, "rb") as f:
            data = f.read()
        rt = time.perf_counter() - t0
        assert len(data) == SIZE
        return SIZE / rt / 1e9, SIZE / wt / 1e9
    finally:
        try:
            os.remove(path)
        except OSError:
            pass


def test_aio_reaches_fraction_of_raw_io(tmp_path):
    # chunk-parallel threads must not LOSE to one plain stream by more
    # than 2.5x (generous: covers O_DIRECT alignment penalties on fast
    # page-cache-backed mounts); a serialized/broken pool lands far
    # lower.  Both sides share the mount with whatever else the host is
    # doing, so one noisy sample is re-measured before failing.
    last = None
    for _ in range(3):
        raw_r, raw_w = _raw_gbps(str(tmp_path))
        aio_r, aio_w = bench_point(str(tmp_path), SIZE, block_size=8 << 20,
                                   thread_count=8, loops=2)
        if aio_r >= 0.4 * raw_r and aio_w >= 0.4 * raw_w:
            return
        last = (aio_r, raw_r, aio_w, raw_w)
    raise AssertionError(f"aio below 0.4x raw after 3 tries: {last}")


def test_aio_combined_floor_vs_reference(tmp_path):
    """Sanity floor: the engine moves data at >= 0.2 GB/s combined even
    on modest CI disks (the reference's no-GDS 11 GB/s combined needs
    4x NVMe hardware)."""
    r, w = bench_point(str(tmp_path), SIZE, block_size=8 << 20,
                       thread_count=8, loops=1)
    assert r + w >= 0.2, (r, w)
