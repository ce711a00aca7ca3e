"""Kernels: the grouped expert matmul's share of the HBM roofline in a
hybrid with LATENT non-gated experts of which a share is held, %: bytes
of expert kernels the traced slice had to read
(costs_nemotron.expert_bytes: the two [1,024 x 2,688] kernels of every
held expert that took a row, once a layer-forward, decode steps AND
prefill chunks, from `moe_experts_hit_total`) / summed device self time
of the `gmm` kernel / the chip's peak bytes/s. A prefill chunk's
products are bound by operations, not bytes (1,024 x 22 rows over the
held quarter of 512 experts), so the share reads low by their part of
the kernel's time, never high. (`.hybrid`, `.whole` and `.held` read
other models' keys and return None here.)

None where the trace has no such kernel, the slice no such counter or
the configuration no latent."""
LAYER = "kernels"
from benchmark import costs_nemotron, program, trace

KERNELS = ("gmm",)


def read(run):
    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("ops", {}), KERNELS)
    hit = tr.get("slice_counters", {}).get("moe_experts_hit_total")
    if not sec or not hit or "moe_latent_size" not in run["config"]:
        return None
    need = hit * costs_nemotron.expert_bytes(run["config"])
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
