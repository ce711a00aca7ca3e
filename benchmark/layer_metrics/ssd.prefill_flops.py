"""Model step: the chunked Mamba-2 scan's share of the chip's bf16 peak
in the prefill, %: operations the scans of the traced slice had to do
(costs_nemotron.ssd_chunk_flops: C B^T a group, its masked product with
x, the chunk's state and the carried state's readout a head, for one
chunk of `chunk_size` tokens through one mixer, x
`ssd_prefill_chunks_total` x the mixers) / the self seconds of
`paged_prefill` under the program's `mixer/ssd_chunk` scope
(scope_table: the decays, the cumulative sums and the products alike) /
the chip's peak operations/s. The decay matrix is elementwise float32
work as large as the products', so the share is expected far under 100;
what it says is what the matmul form costs beside a scan over T.

None where the capture names no such scope (a program without the
mixer, a capture without scopes), the slice no such counter or the
configuration no Mamba-2 mixer."""
LAYER = "model step"
from benchmark import costs_nemotron, program, scope_table

SCOPE = "mixer/ssd_chunk"


def read(run):
    tr = run.get("trace") or {}
    chunks = tr.get("slice_counters", {}).get("ssd_prefill_chunks_total")
    c = run["config"]
    if not chunks or "ssm_state_size" not in c:
        return None
    paths = scope_table.table(run).get("paged_prefill") or {}
    sec = paths.get(SCOPE, (0.0, 0))[0]
    if not sec:
        return None
    need = (chunks * costs_nemotron.sizes(c)["mixers"]
            * costs_nemotron.ssd_chunk_flops(c))
    peak = program.load_peaks()[run["device"]["kind"]]["bf16_flops_per_s"]
    return 100.0 * need / sec / peak
