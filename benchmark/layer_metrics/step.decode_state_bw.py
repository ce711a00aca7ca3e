"""Model step: the decode step's share of the HBM roofline by weights,
recurrent state and KV, %: bytes the decode steps of the traced slice
had to move (costs_ssm.decode_bytes: the weights once a step, the LIVE
lanes' state read and written once a step from
`ssm_decode_lane_steps_total`, the cached tokens their attention read
from `decode_kv_tokens_total`) / device seconds of `paged_decode_chunk`
/ the chip's peak bytes/s. A lower bound of what moved (activations,
the sampler and padding are left out; a lane's last step, which meets
its end-of-sequence token, is not counted), so it cannot pass 100.

None where the slice has no such counter (a program without state-space
layers) or the trace no decode dispatch."""
LAYER = "model step"
from benchmark import costs_ssm, program, trace

PROGRAMS = ("paged_decode_chunk",)


def read(run):
    tr = run.get("trace") or {}
    sec, n = trace.match_seconds(tr.get("modules", {}), PROGRAMS)
    sc = tr.get("slice_counters", {})
    lane_steps = sc.get("ssm_decode_lane_steps_total")
    if not sec or not n or lane_steps is None:
        return None
    c = run["config"]
    need = costs_ssm.decode_bytes(
        c, steps=n * c["layout"]["decode_chunk"], lane_steps=lane_steps,
        kv_tokens=sc.get("decode_kv_tokens_total", 0.0))
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
