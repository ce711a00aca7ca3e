"""Training entry point: `python -m oryx_tpu.train.cli --config cfg.json ...`.

Reference parity: `oryx/train/train.py` `train()` + the `train_mem.py`
launcher invoked as `deepspeed oryx/train/train_mem.py --deepspeed
zero3.json --model_name_or_path ... ` (SURVEY.md §3.1). One process per
HOST (not per chip): jax.distributed rendezvous replaces the deepspeed
launcher; the mesh + shardings in the config replace the ZeRO JSON; the
launch scripts in scripts/ carry the hyperparameters.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax

from oryx_tpu.config import OryxConfig
from oryx_tpu.models import splice
from oryx_tpu.parallel import mesh as mesh_lib
from oryx_tpu.train import data as data_lib
from oryx_tpu.train.trainer import Trainer
from oryx_tpu.utils.metrics import rank0_print


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="Oryx-TPU SFT")
    ap.add_argument("--config", required=True, help="OryxConfig json file")
    ap.add_argument("--data", required=True,
                    help="conversation-records json (LLaVA-mix schema)")
    ap.add_argument("--media-root", default="")
    ap.add_argument("--tokenizer-path", required=True)
    ap.add_argument("--template", default="qwen")
    ap.add_argument("--output-dir", default=None,
                    help="save a loadable model dir here at the end")
    ap.add_argument("--init-from", default=None,
                    help="oryx_tpu model dir to start from (else random init)")
    ap.add_argument("--hf-llm", default=None,
                    help="HF safetensors dir for the LLM backbone")
    ap.add_argument("--hf-vision", default=None,
                    help="HF safetensors dir for the vision tower")
    ap.add_argument("--projector", default=None,
                    help="projector-only npz (stage-1 checkpoint)")
    ap.add_argument("--sharding", default="fsdp",
                    choices=["fsdp", "zero2", "ddp"])
    ap.add_argument("--metrics-path", default=None)
    ap.add_argument("--tensorboard-dir", default=None,
                    help="also report metrics as TensorBoard scalars")
    ap.add_argument(
        "--metrics-port", type=int, default=None,
        help="serve oryx_train_* Prometheus metrics + /healthz + "
        "/readyz on this port (process 0 only; 0 = ephemeral port, "
        "see docs/OBSERVABILITY.md)",
    )
    ap.add_argument(
        "--events-path", default=None,
        help="append structured anomaly events (NaN loss, loss spike, "
        "grad explosion, throughput collapse) as JSONL here",
    )
    ap.add_argument(
        "--on-anomaly", choices=["warn", "halt"], default="warn",
        help="anomaly policy: 'warn' logs + counts and keeps training; "
        "'halt' raises out of the step loop (the pod restarts from the "
        "last checkpoint instead of burning chips on a poisoned run)",
    )
    ap.add_argument(
        "--numerics-every", type=int, default=0, metavar="N",
        help="every N steps the jitted step runs its numerics-probe "
        "twin (per-layer grad absmax, activation/param absmax -> "
        "oryx_numerics_* gauges + the absmax_explosion sentinel); "
        "0 = off",
    )
    ap.add_argument("--num-steps", type=int, default=None)
    ap.add_argument("--video-frames", type=int, default=64)
    # Multi-host rendezvous (auto-detected on TPU pods; explicit for tests).
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    return ap


def load_params(args, cfg: OryxConfig):
    """Initial params per the reference's init flow (SURVEY.md §3.3):
    resume dir > HF backbone+tower import > random init (None)."""
    from oryx_tpu.serve import builder

    if args.init_from:
        _, params, _ = builder.load_pretrained_model(
            args.init_from, tokenizer=object(), cfg=cfg
        )
        return params
    if args.hf_llm and args.hf_vision:
        _, params, _ = builder.load_from_hf(
            args.hf_llm, args.hf_vision, cfg, projector_path=args.projector
        )
        return params
    return None


def main(argv: list[str] | None = None) -> None:
    args = build_argparser().parse_args(argv)
    from oryx_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    from oryx_tpu.utils import faults

    if faults.configure_from_env():
        # $ORYX_FAULTS arms the trainer chaos sites (checkpoint_save/
        # restore, data_loader_next, trainer_crash) — chaos testing
        # only, never a production config.
        rank0_print("fault injection armed from $ORYX_FAULTS")
    if args.coordinator or args.num_processes:
        mesh_lib.initialize_distributed(
            args.coordinator, args.num_processes, args.process_id
        )

    with open(args.config) as f:
        cfg = OryxConfig.from_json(f.read())
    if args.num_steps:
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(
                cfg.train, num_train_steps=args.num_steps
            )
        )

    from transformers import AutoTokenizer

    tokenizer = AutoTokenizer.from_pretrained(
        args.tokenizer_path, use_fast=True
    )

    def media_loader(rec):
        from oryx_tpu.data import media

        frames, _ = media.load_record_media(
            rec, media_root=args.media_root, num_frames=args.video_frames
        )
        return frames

    dataset = data_lib.SupervisedDataset(
        args.data, tokenizer,
        template=args.template,
        patch_size=cfg.vision.patch_size,
        max_patches_per_image=cfg.vision.max_patches_per_image,
        video_frames=args.video_frames,
        media_loader=media_loader,
    )
    rank0_print(f"dataset: {len(dataset)} records")

    # Per-host batch slice (SURVEY.md §2c(c)): each process collates its
    # round-robin share of batches.
    batches = data_lib.grouped_batch_iterator(
        dataset,
        cfg.train.global_batch_size,
        seed=cfg.train.seed,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        grad_accum_steps=cfg.train.grad_accum_steps,
        length_group_size=cfg.train.length_group_size,
        patch_size=cfg.vision.patch_size,
        base_grid=cfg.vision.base_grid,
        max_len=cfg.train.max_seq_len,
        frame_separator_ids=splice.frame_separator_ids(
            tokenizer, cfg.frame_separator
        ),
    )

    trainer = Trainer(
        cfg,
        params=load_params(args, cfg),
        sharding_mode=args.sharding,
        metrics_path=args.metrics_path,
        tensorboard_dir=args.tensorboard_dir,
        metrics_port=args.metrics_port,
        events_path=args.events_path,
        on_anomaly=args.on_anomaly,
        numerics_every=args.numerics_every,
    )
    if trainer.telemetry is not None and trainer.telemetry.port is not None:
        rank0_print(
            f"telemetry: http://127.0.0.1:{trainer.telemetry.port}/metrics"
        )
    state = trainer.fit(batches)

    if args.output_dir:
        from oryx_tpu.serve import builder

        # All processes participate: orbax coordinates the multi-host
        # sharded write (a proc-0-only save would deadlock on remote
        # shards). Export WEIGHTS only — the optimizer moments are 2/3
        # of a TrainState's bytes and cfg.train.checkpoint_dir already
        # holds the resumable full state.
        builder.save_pretrained(
            args.output_dir, cfg, state.params,
            step=int(jax.device_get(state.step)),
        )
        rank0_print(f"saved model to {args.output_dir}")


if __name__ == "__main__":
    main()
