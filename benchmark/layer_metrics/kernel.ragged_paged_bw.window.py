"""Kernels: the paged-attention kernel's share of the HBM roofline over
BOTH planes of a pool with window layers, %: K/V bytes the decode steps
of the traced slice had to read (costs_smallthinker.kv_read_bytes: a
global layer the lanes' whole lengths, `decode_kv_tokens_total`, a
window layer min(length, window), `decode_window_kv_tokens_total`) /
summed device self time of `_ragged_paged` / the chip's peak bytes/s.
(`kernel.ragged_paged_bw` reckons every layer a whole-context layer
from client-side records; this one reads the engine's own counters.)

None where the trace has no such kernel or the slice no such counter (a
program without window layers)."""
LAYER = "kernels"
from benchmark import costs_smallthinker, program, trace

KERNELS = ("_ragged_paged", "ragged_paged")


def read(run):
    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("ops", {}), KERNELS)
    sc = tr.get("slice_counters", {})
    window = sc.get("decode_window_kv_tokens_total")
    if not sec or window is None:
        return None
    need = costs_smallthinker.kv_read_bytes(
        run["config"], kv_tokens=sc.get("decode_kv_tokens_total", 0.0),
        window_kv_tokens=window)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
