"""Host data-pipeline throughput: the reference's DataLoader-floor
analog, measurable without a TPU (this is all host CPU work).

Times visual preprocessing (resize+normalize+patchify, the pipeline's
hot loop) through pack_raw_images on a 64-frame 224px video request —
native C++ path (native/loader.cpp thread pool) vs the pure-numpy
fallback, frames/sec. Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPS = int(os.environ.get("DATA_REPS", "5"))
FRAMES = int(os.environ.get("DATA_FRAMES", "64"))


def _time(fn, reps=REPS):
    fn()  # warm caches / lazy builds
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.percentile(ts, 50))


def main() -> None:
    from oryx_tpu import config as cfg_lib
    from oryx_tpu.data import native_loader
    from oryx_tpu.ops import packing

    cfg = cfg_lib.oryx_tiny()
    rng = np.random.default_rng(0)
    frames = [
        rng.integers(0, 255, size=(224, 224, 3), dtype=np.uint8)
        for _ in range(FRAMES)
    ]

    def pack():
        packing.pack_raw_images(
            frames, patch_size=cfg.vision.patch_size,
            base_grid=cfg.vision.base_grid, side_factors=16,
        )

    # High-res ingest shape (4K video frame -> patch grid): the
    # downscale case where touching only the sampled taps matters.
    img4k = rng.integers(0, 255, size=(2160, 3840, 3), dtype=np.uint8)

    def pack4k():
        packing.pack_raw_images(
            [img4k] * 4, patch_size=cfg.vision.patch_size,
            base_grid=cfg.vision.base_grid, side_factors=16,
        )

    native_built = native_loader.build(quiet=True)
    results = {}
    if native_built and native_loader.is_available():
        results["native_frames_per_s"] = round(FRAMES / _time(pack), 1)
        results["native_4k_ms_per_frame"] = round(_time(pack4k) / 4 * 1e3, 1)
    os.environ["ORYX_NATIVE_LIB"] = "/nonexistent"  # force python fallback
    os.environ["ORYX_NATIVE_AUTOBUILD"] = "0"  # and skip the futile rebuild
    native_loader._lib = None
    native_loader._lib_failed = False
    results["python_frames_per_s"] = round(FRAMES / _time(pack), 1)
    results["python_4k_ms_per_frame"] = round(_time(pack4k) / 4 * 1e3, 1)
    if "native_frames_per_s" in results:
        results["native_speedup"] = round(
            results["native_frames_per_s"] / results["python_frames_per_s"], 2
        )
        results["native_4k_speedup"] = round(
            results["python_4k_ms_per_frame"]
            / results["native_4k_ms_per_frame"], 1
        )

    print(json.dumps({
        "metric": "host_pipeline_throughput",
        "frames": FRAMES,
        "reps": REPS,
        **results,
    }))


if __name__ == "__main__":
    main()
