"""Kernels: the latent page walk's share of the HBM roofline in a model
with ONE cache layer a model layer, %: latent bytes the decode steps of
the traced slice had to read (costs_mla_single.latent_decode_bytes over
`decode_kv_tokens_total`, the sum over steps of the live lanes'
lengths) / summed device self time of `_latent_paged` / the chip's peak
bytes/s. At 32 heads against one shared key of 320 values the op does
2 x (320 + 256) x 32 operations for 640 bytes a token, a quarter of the
chip's ridge, so bytes bound it.

None where the trace has no such kernel or the slice no such counter
(a program without the latent pool)."""
LAYER = "kernels"
from benchmark import costs_mla_single, program, trace

KERNELS = ("_latent_paged",)


def read(run):
    sec, _ = trace.match_seconds(run["trace"].get("ops", {}), KERNELS)
    tokens = run["trace"].get("slice_counters", {}).get(
        "decode_kv_tokens_total")
    if not sec or not tokens:
        return None
    need = costs_mla_single.latent_decode_bytes(
        run["config"], kv_tokens=tokens)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
