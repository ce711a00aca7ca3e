"""Kernels: the paged-attention kernel's share of the HBM roofline where
only SOME layers keep K/V (a gated-short-convolution hybrid), %: K/V
bytes the decode steps of the traced slice had to read
(costs_lfm2.kv_bytes_per_token over the attention layers alone x
`decode_kv_tokens_total`, the live lanes' lengths summed over steps, the
engine's own counter) / summed device self time of `_ragged_paged` / the
chip's peak bytes/s. (`kernel.ragged_paged_bw` reckons every layer a
K/V layer from client-side records: ROADMAP B0(a)(vii).)

None where the trace has no such kernel, the slice no such counter or
the configuration no conv layer."""
LAYER = "kernels"
from benchmark import costs_lfm2, program, trace

KERNELS = ("_ragged_paged", "ragged_paged")


def read(run):
    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("ops", {}), KERNELS)
    tokens = tr.get("slice_counters", {}).get("decode_kv_tokens_total")
    if not sec or not tokens or "conv_L_cache" not in run["config"]:
        return None
    need = tokens * costs_lfm2.kv_bytes_per_token(run["config"])
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
