"""Kernels: the grouped expert matmul's share of the HBM roofline, %:
bytes of expert kernels the block steps of the traced slice had to read
(costs_moe.expert_bytes_read: gate, up and down of every expert that
took a row, once a layer-forward, from `moe_experts_hit_total`) /
summed device self time of the `gmm` kernel (the grouped matmul jax
ships, which `qwen2._grouped_dot` picks under attn_impl "pallas") / the
chip's peak bytes/s. Memory-bound: at 8 rows an expert a product does 16
operations a byte of kernel.

The kernel's seconds hold the prefill chunks' products too, whose
experts the counters do not count (they count the block step's
forwards), so the share reads low by the prefill's part of the kernel's
time (a sixth in the cell), never high.

None where the trace has no such kernel (a program whose grouped
products are XLA's `ragged-dot`) or the slice no such counter."""
LAYER = "kernels"
from benchmark import costs_moe, program, trace

KERNELS = ("gmm",)


def read(run):
    sec, _ = trace.match_seconds(run["trace"]["ops"], KERNELS)
    hit = run["trace"]["slice_counters"].get("moe_experts_hit_total")
    if not sec or not hit:
        return None
    need = costs_moe.expert_bytes_read(run["config"], experts_hit=hit)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
