"""Kernels: the selected rows' read and the attention over them, as a
share of the HBM roofline, %: latent bytes the decode steps of the
traced slice had to read (costs_dsa.selected_row_bytes over
`decode_selected_tokens_total`: min(length, index_topk) rows a decode
row, a layer) / summed device self time of the row read and of the
absorbed attention over the gathered rows / the chip's peak bytes/s.

The attention is the kernel `_latent_paged` (the dense latent cells'
own walk, here over a lane's index_topk gathered rows). The row read is
XLA's gather of `paged_kv.gather_rows`, which the trace names after the
ops XLA emits for it (`GATHER`: an op whose name holds "gather"); where
a compile names it otherwise the read's time is missing from the sum
and the share reads HIGH by it, which PERF.md section 7 says.

None where the trace has no `_latent_paged` or the slice no such counter
(a program without an indexer has no `decode_selected_tokens_total`)."""
LAYER = "kernels"
from benchmark import program, trace

ATTEND = ("_latent_paged",)
GATHER = ("gather",)


def read(run):
    from benchmark import costs_dsa

    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("ops", {}), ATTEND)
    rows = tr.get("slice_counters", {}).get("decode_selected_tokens_total")
    if not sec or not rows:
        return None
    sec += trace.match_seconds(tr.get("ops", {}), GATHER)[0]
    need = costs_dsa.selected_row_bytes(run["config"], selected_tokens=rows)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
