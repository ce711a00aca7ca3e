"""The device's time by the program's own layer names, for the
`scope.*` readers: {program: {scope path: [self seconds, count]}} of the
traced slice, read off the raw capture with the PROGRAM's reader and
vocabulary (`oryx_tpu.utils.xplane.scope_seconds` over
`profiling.DEVICE_SCOPES`: an `XLA Ops` event's op_name holds the
`jax.named_scope`s its op was traced under). The compiler's op names
(`fusion.693`) change with every compile; a scope path (`attn/mla`)
changes when the program's layers do.

`run.py` sets `run["cell"]` before the readers are called, and every
runner leaves the capture at `benchmark/out/<cell>/trace/**/*.xplane.pb`
until the NEXT run of the cell starts. Parsing it a second time costs
seconds a run, after every timed window, and once: the eight readers
share one table.

{} where there is nothing to read: no capture, no device plane (the
CPU rehearsal), or a program that names no scopes (a checkout older
than the vocabulary). A capture whose executables came out of a compile
cache filled BEFORE the scopes were written carries none of them
either (jax's cache key leaves debug info out): `unscoped` is then
all there is."""

from __future__ import annotations

import functools
import glob
import os

HERE = os.path.dirname(os.path.abspath(__file__))
# The program a cell's steady state runs: the first of these the table
# holds. (`paged_prefill` is read beside it, never as the step.)
STEP_PROGRAMS = ("paged_block_step", "paged_decode_chunk", "train_step_fn")
PREFILL = ("paged_prefill",)
UNSCOPED = "unscoped"


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime: float) -> dict:
    from oryx_tpu.utils import profiling, xplane

    scope_table = getattr(profiling, "scope_table", None)
    if scope_table is None:
        return {}
    return scope_table(xplane.parse_xspace(path))


def table(run) -> dict:
    files = sorted(glob.glob(os.path.join(
        HERE, "out", run["cell"], "trace", "**", "*.xplane.pb"),
        recursive=True))
    if not files:
        return {}
    return _read(files[-1], os.path.getmtime(files[-1]))


def share(run, scopes, programs=STEP_PROGRAMS):
    """Self seconds under `scopes` (top-level names, or whole paths)
    over the summed self seconds of the first of `programs` the table
    holds, %. None where it holds none of them, or the scopes have no
    second there."""
    tab = table(run)
    paths = next((tab[p] for p in programs if p in tab), None)
    if not paths:
        return None
    sec = sum(v[0] for path, v in paths.items()
              if path in scopes or path.split("/")[0] in scopes)
    total = sum(v[0] for v in paths.values())
    return 100.0 * sec / total if sec and total else None
