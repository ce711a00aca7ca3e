"""Per-layer metric readers live one to a file, named by the metric
(`layer_metrics/<name>.py`, dots and all), so they are loaded by path.

A metric's `moves` must be an end-to-end metric of every cell that
reports it, so a quantity read in cells that are judged by different
end-to-end metrics stands in BENCHMARK.json once per group of cells,
as `<name>` and `<name>.<group>` (`sched.decode_util`,
`sched.decode_util.batch`). Both are the one reader `<name>.py`: a
name with no file of its own is looked up without its last part."""

from __future__ import annotations

import importlib.util
import os

DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "layer_metrics")


def load(name: str):
    path = os.path.join(DIR, name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(DIR, name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
