"""Kernels: the flash attention kernels' share of the bf16 FLOP
roofline in training, %: the attention FLOPs the algorithm needs in
the traced steps — decoder causal forward + backward over the rows
(costs.attention_flops_causal), the ViT's forward within each image
and its backward only when the tower trains (costs.attention_flops_full;
in-kernel recomputation never counted) — / summed device time of every
`_mha_forward` + `_mha_backward` call / the chip's peak. Both towers
call the same kernels; compute-bound at T 2048, D 128."""
LAYER = "kernels"
from benchmark import program, trace

KERNELS = ("_mha_forward", "_mha_backward", "mha_forward", "mha_backward")


def read(run):
    sec, _ = trace.match_seconds(run["trace"]["ops"], KERNELS)
    if not sec:
        return None
    peak = program.load_peaks()[run["device"]["kind"]]["bf16_flops_per_s"]
    flops = run["train"]["flash_flops_traced"]
    return 100.0 * flops / sec / peak
