"""Kernels: the grouped expert matmul's share of the HBM roofline in a
gated-short-convolution hybrid whose every expert is held, %: bytes of
expert kernels the traced slice had to read (costs_lfm2.expert_bytes:
gate, up and down of every expert that took a row, once a
layer-forward, decode steps AND prefill chunks, from
`moe_experts_hit_total`) / summed device self time of the `gmm` kernel
/ the chip's peak bytes/s. A prefill chunk's products are bound by
operations, not bytes (512 x 4 rows over 64 experts), so the share reads
low by their part of the kernel's time, never high. (`.whole` reads
SmallThinker's keys and returns None here.)

None where the trace has no such kernel, the slice no such counter or
the configuration no conv layer."""
LAYER = "kernels"
from benchmark import costs_lfm2, program, trace

KERNELS = ("gmm",)


def read(run):
    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("ops", {}), KERNELS)
    hit = tr.get("slice_counters", {}).get("moe_experts_hit_total")
    if not sec or not hit or "conv_L_cache" not in run["config"]:
        return None
    need = hit * costs_lfm2.expert_bytes(run["config"])
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
