"""Kernels: the latent page walk's share of the HBM roofline, %: latent
bytes the decode steps of the traced slice had to read
(costs_mla.latent_decode_bytes over `decode_kv_tokens_total`, the sum
over steps of the live lanes' lengths, which the program counts from
the lengths its decode step ran with) / summed device self time of
`_latent_paged` / the chip's peak bytes/s. At 64 heads against one
shared key the op does 2 x (576 + 512) x 64 operations for 1,152 bytes
a token, half the ridge of the chip, so it is bound by neither alone;
costs_mla.latent_decode_flops gives its share of the peak operations
for PERF.md.

None where the trace has no such kernel or the slice no such counter
(a program without the latent pool)."""
LAYER = "kernels"
from benchmark import costs_mla, program, trace

KERNELS = ("_latent_paged",)


def read(run):
    sec, _ = trace.match_seconds(run["trace"]["ops"], KERNELS)
    tokens = run["trace"]["slice_counters"].get("decode_kv_tokens_total")
    if not sec or not tokens:
        return None
    need = costs_mla.latent_decode_bytes(run["config"], kv_tokens=tokens)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
