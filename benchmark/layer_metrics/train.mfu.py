"""Trainer: model FLOP/s utilisation, %: the benchmark's model FLOPs
of the window's steps (costs.train_step_model_flops: matmul parameters x tokens,
causal attention fwd + bwd, the vision tower; remat and the frozen
base's weight gradients not counted) / window / (chips x the
chip's bf16 peak)."""
LAYER = "trainer"
from benchmark import program


def read(run):
    t = run["train"]
    peaks = program.load_peaks().get(run["device"]["kind"])
    if peaks is None:  # the CPU rehearsal; a measurement refuses the kind
        return None
    peak = peaks["bf16_flops_per_s"]
    rate = t["model_flops"] / t["window_s"]
    return 100.0 * rate / (t["chips"] * peak)
