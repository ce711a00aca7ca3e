"""Kernels: the prefill attention kernel's share of the chip's peak
operations, %: what the prefill chunks of the traced slice had to do
(costs_smallthinker.prefill_attention_flops: 4 x head x query heads a
visible pair, `prefill_attn_pairs_total` pairs a global layer,
`prefill_window_attn_pairs_total` a window layer, whose query sees its
window alone) / summed device self time of `_mha_forward` / the chip's
peak bf16 operations/s. The kernel also computes the masked half of
every diagonal tile and, on a window layer, the tiles of the band's
edge, so the share reads under the kernel's own utilisation.

None where the trace has no such kernel or the slice no such counter (a
program without window layers)."""
LAYER = "kernels"
from benchmark import costs_smallthinker, program, trace

KERNELS = ("_mha_forward",)


def read(run):
    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("ops", {}), KERNELS)
    sc = tr.get("slice_counters", {})
    window = sc.get("prefill_window_attn_pairs_total")
    if not sec or not window:
        return None
    need = costs_smallthinker.prefill_attention_flops(
        run["config"], pairs=sc.get("prefill_attn_pairs_total", 0.0),
        window_pairs=window)
    peak = program.load_peaks()[run["device"]["kind"]]["bf16_flops_per_s"]
    return 100.0 * need / sec / peak
