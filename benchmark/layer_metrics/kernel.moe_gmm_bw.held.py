"""Kernels: the grouped expert matmul's share of the HBM roofline where
a chip holds a SHARE of the routed experts, %: bytes of expert kernels
the traced slice's forwards had to read
(costs_mla_single.held_expert_bytes: gate, up and down of every HELD
expert that took a row, once a layer-forward, from
`moe_held_experts_hit_total` of the decode steps and
`moe_prefill_held_experts_hit_total` of the prefill chunks) / summed
device self time of the `gmm` kernel (the grouped matmul jax ships,
which `qwen2._grouped_dot` picks under attn_impl "pallas") / the chip's
peak bytes/s. Memory-bound in both programs here: a prefill chunk of
1,024 rows gives a held expert 32 rows, 32 operations a byte of kernel
against the chip's 240.

A prefill chunk's experts are counted at the engine's next read that
waits anyway (the decode chunk's harvest or a prompt's first token), a
round after its kernels ran at most: a slice of 3 s is some thirty
rounds.

None where the trace has no such kernel (a program whose grouped
products are XLA's `ragged-dot`) or the slice no such counters (a
program before PR 33)."""
LAYER = "kernels"
from benchmark import costs_mla_single, program, trace

KERNELS = ("gmm",)


def read(run):
    sec, _ = trace.match_seconds(run["trace"].get("ops", {}), KERNELS)
    sc = run["trace"].get("slice_counters", {})
    decode, prefill = (sc.get("moe_held_experts_hit_total"),
                       sc.get("moe_prefill_held_experts_hit_total"))
    if not sec or decode is None or prefill is None:
        return None
    need = costs_mla_single.held_expert_bytes(
        run["config"], held_hit=decode + prefill)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
