"""Model step: the block step's share of the HBM roofline, %: weight
bytes the forwards of the traced slice had to read
(benchmark/costs_moe.py: attention, router and head once a forward, and
every expert that took a row once per layer-forward, from
`moe_experts_hit_total`) / device seconds of `paged_block_step` / the
chip's peak bytes/s. Memory-bound: 0.18 TFLOP a forward is 0.9 ms at
197 TFLOP/s against 11.4 ms of bytes. It is the roofline share of the
whole program that holds the grouped expert products; the products'
own share is `kernel.moe_gmm_bw`.

A forward that only commits reads no head (nobody reads its logits) and
is billed without one. Since PR 39 a block's commit rides the next
block's first denoising forward, the program keeps the series
`diffusion_forwards_total{kind="commit"}` at 0 and every forward has a
head: 0 is a count like any other, only a program WITHOUT the series
gives None. (The commit lanes' embedding rows, 0.5 MB a forward beside
9 GB of weights, are not billed.)

None where the trace has no such program or the counters no forwards."""
LAYER = "model step"
from benchmark import costs_moe, program, trace

PROGRAMS = ("paged_block_step",)


def read(run):
    sec, _ = trace.match_seconds(run["trace"]["modules"], PROGRAMS)
    sc = run["trace"]["slice_counters"]
    denoise = sc.get('diffusion_forwards_total{kind="denoise"}')
    commit = sc.get('diffusion_forwards_total{kind="commit"}')
    hit = sc.get("moe_experts_hit_total")
    if not sec or commit is None or denoise is None or hit is None:
        return None
    c, lay = run["config"], run["config"]["layout"]
    rows = lay["num_slots"] * lay["block_length"]
    fixed = lambda head: costs_moe.forward_weight_bytes(  # noqa: E731
        c, experts_hit=0, rows=rows, head=head)
    need = (denoise * fixed(True) + commit * fixed(False)
            + hit * costs_moe.expert_params(c) * 2)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
