"""Kernels: the Mamba-2 decode-step kernel's share of the HBM roofline,
%: bytes the state updates of the traced slice had to move
(costs_nemotron.ssd_step_bytes: a live lane's S [N, d_in] float32 read
once and written once, 2 x 4,194,304 B at the published widths, a mixer
a live lane-step: `ssm_decode_lane_steps_total` x the mixers) / summed
device self time of `_ssd_step` / the chip's peak bytes/s. The state is
ALL of the kernel's HBM traffic (a dead lane's rows are neither read nor
written), so the share says how near the step's state traffic runs to
the chip's rate.

None where the trace has no such kernel, the slice no such counter or
the configuration no Mamba-2 mixer."""
LAYER = "kernels"
from benchmark import costs_nemotron, program, trace

KERNELS = ("_ssd_step",)


def read(run):
    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("ops", {}), KERNELS)
    steps = tr.get("slice_counters", {}).get("ssm_decode_lane_steps_total")
    c = run["config"]
    if not sec or not steps or "ssm_state_size" not in c:
        return None
    need = (steps * costs_nemotron.sizes(c)["mixers"]
            * costs_nemotron.ssd_step_bytes(c))
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
