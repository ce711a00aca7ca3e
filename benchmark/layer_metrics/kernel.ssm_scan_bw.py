"""Kernels: the selective scan's share of the HBM roofline, %: bytes the
prefill scans of the traced slice had to move (costs_ssm.scan_bytes
over `ssm_prefill_tokens_total` real tokens and the slice's prefill
dispatches: x, z in and y out in the serving dtype, dt, B, C float32,
the state once in and once out a chunk a layer) / summed device self
time of `_selective_scan` / the chip's peak bytes/s. The recurrence is
VPU work (an exp and five multiply-adds a state element a token, 16
state elements a channel), so the share is expected far under 100.

None where the trace has no such kernel or the slice no such counter
(a program without state-space layers)."""
LAYER = "kernels"
from benchmark import costs_ssm, program, trace

KERNELS = ("_selective_scan",)


def read(run):
    tr = run.get("trace") or {}
    sec, calls = trace.match_seconds(tr.get("ops", {}), KERNELS)
    tokens = tr.get("slice_counters", {}).get("ssm_prefill_tokens_total")
    if not sec or not tokens:
        return None
    c = run["config"]
    # One call a Mamba layer a chunk.
    chunks = calls / costs_ssm.sizes(c)["mamba_layers"]
    need = costs_ssm.scan_bytes(c, tokens=tokens, chunks=chunks)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
