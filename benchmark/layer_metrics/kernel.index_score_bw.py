"""Kernels: the index-score stage's share of the HBM roofline, %: index
key bytes the decode steps of the traced slice had to read
(costs_dsa.index_key_bytes over `decode_kv_tokens_total`, the sum over
steps of the live lanes' lengths: every cached key of a lane is scored
once a layer) / summed device self time of `_dsa_index` (the Pallas
page walk of ops/pallas/paged_attention.py; the stage is that one
kernel) / the chip's peak bytes/s. At 32 index heads against one key of
128 values the op does 2 x 128 x 32 operations for 256 bytes a token,
an eighth of the chip's ridge, so bytes bound it.

None where the trace has no such kernel or the slice no such counter (a
program without an indexer, as the parent commit)."""
LAYER = "kernels"
from benchmark import program, trace

KERNELS = ("_dsa_index",)


def read(run):
    from benchmark import costs_dsa

    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("ops", {}), KERNELS)
    tokens = tr.get("slice_counters", {}).get("decode_kv_tokens_total")
    if not sec or not tokens:
        return None
    need = costs_dsa.index_key_bytes(run["config"], kv_tokens=tokens)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
