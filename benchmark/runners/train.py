"""A training cell: Trainer.fit on the configuration's shipped recipe,
one process, steps timed on the benchmark's own clock.

The batches come through an iterator the benchmark owns: each
`__next__` on the main thread is the instant the previous step ended
(fit has just pulled the step's metrics to the host, which blocks until
the device is done) and the next begins, so the window is a whole
number of steps and every second of it is counted. Batches are built on
a background thread by the trainer's own PrefetchIterator while the
device runs. The window ends by raising out of fit (a BaseException,
like the kill a real job gets), so the end-of-fit checkpoint is not
written: a run's disk write is not what train_tok_s measures.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import time

import numpy as np

from benchmark import correctness, costs, program, train_data


class WindowOver(BaseException):
    pass


class Batches:
    """Iterator handed to Trainer.fit (prefetch=0: this IS the
    prefetching). Yields `warm` warm-up batches, then arms the window
    and yields until `seconds` have passed, then raises WindowOver."""

    def __init__(self, plans, build, *, warm: int, seconds: float,
                 on_arm, trace=None):
        from oryx_tpu.train.data import PrefetchIterator

        self.plans = plans
        self.src = PrefetchIterator((build(p) for p in plans), depth=2)
        self.warm, self.seconds, self.on_arm = warm, seconds, on_arm
        self.stamps: list[float] = []
        self.n = 0
        self.trace = trace  # (start_step, steps, start_fn, stop_fn)
        self.trace_t: dict = {}

    def __iter__(self):
        return self

    def __next__(self):
        now = time.monotonic()
        k = self.n - self.warm  # measured steps completed so far
        if k == 0:
            self.on_arm()
            now = time.monotonic()
        if k >= 0:
            self.stamps.append(now)
            if self.trace:
                a, steps, start, stop = self.trace
                if k == a:
                    start()
                    self.trace_t["start"] = time.monotonic()
                    self.stamps[-1] = self.trace_t["start"]
                elif k == a + steps:
                    self.trace_t["stop"] = time.monotonic()
                    stop()
                    self.stamps[-1] = time.monotonic()
            if now - self.stamps[0] >= self.seconds and k >= 2:
                raise WindowOver
        try:
            batch = next(self.src)
        except StopIteration:
            raise WindowOver from None
        self.n += 1
        return batch

    def close(self):
        self.src.close()


def run(ctx: dict) -> dict:
    wl, conf = ctx["workload"], ctx["config"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    bp = wl["batch"]
    cache_dir = program.configure_cache()
    device = program.device_record(chips, rehearse=ctx["rehearse"])

    import jax
    import jax.numpy as jnp

    from oryx_tpu.analysis.sanitizers import recompile_watchdog
    from oryx_tpu.constants import IGNORE_INDEX
    from oryx_tpu.train.trainer import Trainer

    ckpt_dir = os.path.join(ctx["out_dir"], "ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    os.makedirs(ckpt_dir)
    conf = dict(conf, layout=dict(conf["layout"], train=dict(
        conf["layout"].get("train", {}), checkpoint_dir=ckpt_dir,
        log_every=1, seed=seed % (2**31 - 1),
        grad_accum_steps=bp.get("grad_accum_steps", 1),
    )))
    cfg = program.build_config(conf)
    lay = conf["layout"]
    setup = {"cache_dir": cache_dir}

    # Enough plans for the fastest plausible run; building is lazy.
    n_plans = wl.get("warm_steps", 2) + int(
        seconds * wl.get("max_steps_per_s", 4)) + 4
    plans = train_data.plan(bp, seed, n_plans)
    build = lambda p: train_data.build(p, cfg, bp)  # noqa: E731
    check_batch = build(plans[0])

    params = None
    problems = []
    t0 = time.monotonic()
    if lay.get("params") == "seeded_bf16":
        # A loaded checkpoint's layout: bf16 frozen base, what trains
        # (the compressor / projector) in fp32.
        params = program.seeded_params(cfg, seed, "bfloat16")
        params = {**params, "compressor": jax.tree.map(
            lambda x: x.astype(jnp.float32), params["compressor"])}
        check_params = params
    else:
        # The trainer makes its own fp32 weights from cfg.train.seed;
        # cast to bf16 they are what the step computes with, and that
        # is init_params(dtype=bf16) of the same key: made here on
        # device 0 for the check, dropped before the trainer is built.
        with jax.default_device(jax.devices()[0]):
            check_params = program.seeded_params(cfg, seed, "bfloat16")
    setup["init_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    with jax.default_device(jax.devices()[0]):
        check = correctness.train_reference_check(
            check_params, cfg, check_batch, ignore_index=IGNORE_INDEX,
            rows=wl.get("reference_rows", 1),
        )
    del check_params
    gc.collect()
    setup["reference_s"] = time.monotonic() - t0
    if not check["ok"]:
        problems.append(f"forward loss differs from the plain "
                        f"reference: {check}")
    metrics_path = os.path.join(ctx["out_dir"], "metrics.jsonl")
    if os.path.exists(metrics_path):
        os.remove(metrics_path)
    t0 = time.monotonic()
    trainer = Trainer(cfg, params=params, metrics_path=metrics_path)
    del params
    setup["trainer_s"] = time.monotonic() - t0

    stack = contextlib.ExitStack()
    wd_box: dict = {}
    t_arm: dict = {}

    def on_arm():
        wd_box["wd"] = stack.enter_context(
            recompile_watchdog(budget=10**9, action="record"))
        t_arm["setup_s"] = time.monotonic() - ctx["t_start"]

    trace_dir = os.path.join(ctx["out_dir"], "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace = None
    if ctx["trace"]:
        def start():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

        trace = (2, wl.get("trace_steps", 3), start, jax.profiler.stop_trace)
    batches = Batches(
        [plans[0]] * wl.get("warm_steps", 2) + plans[1:], build,
        warm=wl.get("warm_steps", 2), seconds=seconds, on_arm=on_arm,
        trace=trace,
    )
    t0 = time.monotonic()
    try:
        trainer.fit(batches, num_steps=10**9, resume=False, prefetch=0)
    except WindowOver:
        pass
    finally:
        stack.close()
        batches.close()
        trainer.close()
        trainer.ckpt.close()
    setup["fit_s"] = time.monotonic() - t0
    with open(metrics_path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    warm = wl.get("warm_steps", 2)
    stamps = batches.stamps
    steps = len(stamps) - 1
    window_s = stamps[-1] - stamps[0]
    tokens = steps * train_data.tokens_of(plans[0], bp["seq_len"])
    losses = [r["loss"] for r in recs]
    step_s = [b - a for a, b in zip(stamps, stamps[1:])]
    wd = wd_box.get("wd")
    compiles = int(wd.total) if wd else None
    if compiles:
        problems.append(f"{compiles} compiles inside the window: "
                        f"{dict(wd.counts)}")
    if steps < 1 or len(losses) < warm + steps:
        problems.append(f"{steps} steps timed, {len(losses)} losses logged")
    if not np.all(np.isfinite(losses)):
        problems.append("a loss is not finite")
    # Step 0 (the first warm-up step, on the check batch, before any
    # update reaches the weights: lr is 0 at step 1 by warm-up) against
    # the program's own forward-only loss of the same batch.
    if check.get("program_loss_full") is not None and losses:
        d = abs(losses[0] - check["program_loss_full"])
        check["step0_loss"] = losses[0]
        check["step0_abs_diff"] = d
        if d > correctness.LOSS_REL_TOL * abs(check["program_loss_full"]):
            problems.append(f"step-0 loss {losses[0]} against forward-only "
                            f"{check['program_loss_full']}")
    tr = {}
    if ctx["trace"] and batches.trace_t.get("stop"):
        from benchmark import trace as trace_lib

        tr = trace_lib.reduce_dir(
            trace_dir,
            window_s=batches.trace_t["stop"] - batches.trace_t["start"])
        tr["slice_steps"] = wl.get("trace_steps", 3)
    dev = dict(device, memory_peak_bytes=program.memory_peak_bytes())
    if tr:
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
    c = dict(conf, tune=cfg.train.tune)
    vit_trains = cfg.train.tune in ("full",)
    per_step_tokens = train_data.tokens_of(plans[0], bp["seq_len"])

    def flops_of(plan):
        """(model FLOPs, flash-kernel FLOPs) the algorithm needs for one
        step on this plan's rows."""
        seqs = [bp["seq_len"]] * len(plan)
        imgs = [(r["side"] // bp.get("patch", 14)) ** 2
                for r in plan if r["side"]]
        vision = costs.vit_flops(conf["vision"], imgs, backward=vit_trains)
        flash = costs.attention_flops_causal(
            seqs, hq=conf["num_attention_heads"], d=conf["head_dim"],
            layers=conf["num_hidden_layers"], backward=True,
        ) + costs.attention_flops_full(
            imgs, h=conf["vision"]["num_heads"], d=conf["vision"]["head_dim"],
            layers=conf["vision"]["num_layers"], backward=vit_trains)
        return costs.train_step_model_flops(
            c, per_step_tokens, seqs, vision_flops=vision), flash

    done = [flops_of(p) for p in plans[1:1 + max(steps, 0)]]
    a = trace[0] if trace else 0
    traced = done[a:a + wl.get("trace_steps", 3)]
    return {
        "correct": not problems, "problems": problems,
        "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_tok_s": tokens / window_s / chips if steps > 0 else None,
            "setup_s": t_arm.get("setup_s"),
        },
        "device": dev, "trace": tr, "counters": {}, "lateness_ms": [],
        "train": {
            "steps": steps, "window_s": window_s, "tokens": tokens,
            "tokens_per_step": per_step_tokens, "step_s": step_s,
            "program_step_s": [r.get("dispatch_s", 0) + r.get("sync_s", 0)
                               for r in recs[warm:]],
            "data_s": [r.get("data_s", 0) for r in recs[warm:]],
            "losses": losses[:8], "first_loss": losses[0] if losses else None,
            "model_flops": sum(m for m, _ in done), "chips": chips,
            "flash_flops_traced": sum(f for _, f in traced),
            "check": check,
        },
        "setup": setup, "compiles_in_window": compiles,
    }
