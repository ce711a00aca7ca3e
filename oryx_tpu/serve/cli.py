"""Inference CLI: `python -m oryx_tpu.serve.cli --model-path ... --image ...`.

Reference parity: the README inference example / demo CLI (SURVEY.md §2
"Inference example / demo"). Video input is a directory of frame images or
any file decodable by PIL per frame; native video decode (decord/ffmpeg)
stays an optional host-side dependency (SURVEY.md §2a last row).
"""

from __future__ import annotations

import argparse
import sys

from oryx_tpu.data import media


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description="Oryx-TPU inference")
    ap.add_argument("--model-path", required=True)
    ap.add_argument("--tokenizer-path", default=None)
    ap.add_argument(
        "--question", default=None,
        help="one-shot question (omit with --interactive)",
    )
    ap.add_argument(
        "--interactive", action="store_true",
        help="multi-turn REPL over the given media (reference CLI loop); "
        "':reset' clears history, ':q' exits",
    )
    ap.add_argument("--image", action="append", default=[],
                    help="image path (repeatable)")
    ap.add_argument("--video", default=None,
                    help="video file (decord) or directory of frames")
    ap.add_argument("--num-frames", type=int, default=64)
    ap.add_argument("--max-new-tokens", type=int, default=None)
    ap.add_argument("--template", default="qwen")
    ap.add_argument(
        "--shard", default=None, metavar="MODE=N",
        help="multi-chip serving over all visible devices, e.g. tp=8 or "
        "fsdp=8 (34B-class models; the reference's device_map analog)",
    )
    ap.add_argument(
        "--quantize", default=None, choices=["int8"],
        help="weight-only int8 for single-chip serving (halves weight "
        "HBM; mutually exclusive with --shard)",
    )
    args = ap.parse_args(argv)
    from oryx_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.question is None and not args.interactive:
        ap.error("--question is required unless --interactive")
    if args.quantize and args.shard:
        ap.error("--quantize is single-chip serving; drop --shard")

    from oryx_tpu.parallel.mesh import parse_shard_arg
    from oryx_tpu.serve.builder import load_pipeline
    from oryx_tpu.serve.pipeline import ChatSession

    try:
        mesh, mode = parse_shard_arg(args.shard)
    except ValueError as e:
        ap.error(str(e))
    pipe = load_pipeline(
        args.model_path, tokenizer_path=args.tokenizer_path,
        mesh=mesh, sharding_mode=mode, template=args.template,
        quantize=args.quantize,
    )

    if args.video is not None:
        images = media.load_video_frames(args.video, args.num_frames)
        is_video = True
    else:
        images = [media.load_image(p) for p in args.image]
        is_video = False

    if args.interactive:
        # shared=True: a `:reset` (or a future session over the same
        # media) re-seeds from the pipe-level prefix index instead of
        # cold-prefilling the media + system prompt again.
        session = ChatSession(
            pipe, images=images, is_video=is_video, shared=True
        )

        def answer(q: str) -> None:
            print("assistant: ", end="", flush=True)
            for delta in session.ask_stream(
                q, max_new_tokens=args.max_new_tokens
            ):
                print(delta, end="", flush=True)
            print()

        if args.question:
            print(f"user: {args.question}")
            answer(args.question)
        while True:
            try:
                q = input("user: ").strip()
            except EOFError:
                break
            if q in (":q", ":quit", ":exit"):
                break
            if q == ":reset":
                session.reset()
                continue
            if not q:
                continue
            answer(q)
        return

    answer = pipe.chat(
        args.question, images=images or None, is_video=is_video,
        max_new_tokens=args.max_new_tokens,
    )
    print(answer)


if __name__ == "__main__":
    main(sys.argv[1:])
