"""Kernels: the paged-attention kernel's share of the HBM roofline, %:
bytes of KV pages the decode steps in the traced slice had to read
(costs.paged_kv_bytes over the client-side records) / summed device
time of `_ragged_paged` / the chip's peak bytes/s. Memory-bound: the
kernel streams K and V once and does 2 FLOPs a byte per query head."""
LAYER = "kernels"
from benchmark import program, trace

KERNELS = ("_ragged_paged", "ragged_paged")


def read(run):
    sec, _ = trace.match_seconds(run["trace"]["ops"], KERNELS)
    if not sec:
        return None
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * run["trace"]["slice_kv_bytes"] / sec / peak
