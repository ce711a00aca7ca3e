"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py [--seed N]        # one TPU chip, one process
    python chip_smoke.py --chips 4         # the cross-chip paths only

One process drives the main path once through the entry points a user
would call, at Oryx-7B published widths (decoder 3584 / 18944 / 28 q
heads / 4 kv heads / d128 / vocab 152064, the full OryxViT 1152x27 at
head_dim 72, the compressor), bf16, `attn_impl="pallas"`, random
weights from `--seed`, decoder DEPTH cut to what the chip holds:

  kernels  each main-path Pallas kernel against its XLA reference
           (scripts/tpu_validate.parity_cases, in-process);
  serve    OryxInference -> api_server.build_server(engine="continuous")
           on a thread, real HTTP /v1/chat/completions requests (short
           text, a prompt spanning several prefill chunks, one streamed,
           one with an image), once on the split path and once with
           ragged=True; then the same requests twice more (prompts now
           in the prefix cache): those two passes must give the same
           token ids with zero compiles;
  train    a few Trainer.fit steps of the shipped LoRA recipe on a
           seed-made image + text batch.

With `--chips 4` it runs instead the two paths users depend on across
chips — the fsdp=4 full-tune trainer and the tp=4 sharded engine — and
what they are compared with.

It fails (non-zero exit, `"ok": false`) off a TPU and never sets
JAX_PLATFORMS. One JSON line per phase; the LAST stdout line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
These are smoke observations of one run, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import base64
import dataclasses
import gc
import importlib.util
import io
import json
import os
import re
import shutil
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import numpy as np

# Logit rows of two implementations of one model (Pallas against XLA,
# four chips against one) agree to bf16 rounding accumulated over the
# stack: bound the max abs difference by this fraction of the row's own
# max magnitude (floored at 1). Random weights give near-flat logits,
# so greedy ids may differ across implementations — printed, never
# required.
LOGIT_REL_TOL = 5e-2


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def memory() -> dict:
    """Per-device bytes in use / peak (peak is since process start)."""
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append({
            "in_use": st.get("bytes_in_use"),
            "peak": st.get("peak_bytes_in_use"),
        })
    return {"memory_bytes": out}


class IdTokenizer:
    """In-repo tokenizer stand-in (the sealed machine has no checkpoint):
    text encodes one id per character; every id decodes to its own
    `<id>` string, so a reply's text names its token ids exactly."""

    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 50_000) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


def reply_ids(text: str) -> list[int]:
    return [int(m) for m in re.findall(r"<(\d+)>", text)]


# ---------------------------------------------------------------------------
# What the phases run at
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Size:
    cfg: object  # OryxConfig for serve (train derives its own)
    depth_note: str
    kernel_seq: int
    num_slots: int
    page_size: int
    max_ctx: int
    prefill_chunk: int
    decode_chunk: int
    long_prompt_chars: int
    max_tokens: int
    image_side: int  # the asset image is resized to this before sending
    train_cfg_path: str | None  # shipped recipe (None = derive from cfg)
    train_rows: int  # image rows + as many text rows
    train_text_len: int
    train_steps: int


# Decoder depths, each the deepest that a compile for a described v5e
# (TPU rehearsal, PR 21) showed to fit. One chip: the LoRA train step
# holds the bf16 base (6.8 GB of arguments at depth 6) and, because
# grad_norm reads them, full-size weight gradients of the frozen base
# as well — 12.5 GB at depth 6, refused at depth 8 (15.91 of 15.75 GB).
ONE_CHIP_DEPTH = 6
FSDP4_DEPTH = 4


def chip_size():
    from oryx_tpu import config as cfg_lib

    cfg = cfg_lib.oryx_7b()
    cfg = dataclasses.replace(
        cfg,
        llm=dataclasses.replace(cfg.llm, num_layers=ONE_CHIP_DEPTH),
        dtype="bfloat16", attn_impl="pallas",
    )
    return Size(
        cfg=cfg,
        depth_note=(
            f"decoder depth cut 28 -> {ONE_CHIP_DEPTH}: 6 layers + "
            "embeddings + head are 2.49 B params = 5.0 GB in bf16 "
            "beside 0.9 GB of ViT + compressor; the LoRA train step on "
            "the same weights needs 12.5 GB at this depth and is "
            "refused at depth 8 (chip-compiler rehearsal); every width "
            "is the published one"
        ),
        kernel_seq=2048, num_slots=4, page_size=64, max_ctx=2048,
        prefill_chunk=256, decode_chunk=8, long_prompt_chars=700,
        max_tokens=16, image_side=448,
        train_cfg_path=os.path.join(
            ROOT, "scripts", "configs", "oryx_7b_sft_lora.json"
        ),
        train_rows=2, train_text_len=512, train_steps=3,
    )


def tiny_size():
    """The CPU rehearsal: same code, oryx_tiny, kernels interpreted."""
    from oryx_tpu import config as cfg_lib

    cfg = dataclasses.replace(cfg_lib.oryx_tiny(), attn_impl="pallas")
    return Size(
        cfg=cfg, depth_note="oryx_tiny rehearsal (no cut)",
        kernel_seq=128, num_slots=2, page_size=16, max_ctx=256,
        prefill_chunk=32, decode_chunk=4, long_prompt_chars=80,
        max_tokens=4, image_side=56, train_cfg_path=None,
        train_rows=1, train_text_len=24, train_steps=2,
    )


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _tpu_validate():
    spec = importlib.util.spec_from_file_location(
        "tpu_validate", os.path.join(ROOT, "scripts", "tpu_validate.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def phase_kernels(size: Size) -> None:
    import jax

    t0 = time.perf_counter()
    ok, records = _tpu_validate().parity_cases(
        size.kernel_seq, e2e=False, page_size=size.page_size
    )
    emit(
        "kernels", cases=len(records),
        failed=[r["case"] for r in records if not r["pass"]],
        seconds=round(time.perf_counter() - t0, 2), **memory(),
    )
    if not ok:
        raise SystemExit("kernels phase: parity FAILED (cases above)")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


class ProgramRecorder:
    """Remembers the abstract arguments of each jitted step program the
    engine dispatches (by wrapping the module attribute the scheduler
    looks up), so the phase can compile the same program again and read
    which kernels are in it."""

    def __init__(self, module, names):
        self.module, self.names = module, names
        self.calls: dict[str, dict] = {}
        self._orig = {}

    def __enter__(self):
        import jax

        def abstract(x):
            if isinstance(x, jax.Array):
                # An uncommitted array follows the others' devices.
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if x.committed else None,
                )
            return x

        for name in self.names:
            orig = getattr(self.module, name)
            self._orig[name] = orig

            def wrapper(*a, __orig=orig, __name=name, **kw):
                # One cheap key per dispatch (what jit itself keys on,
                # roughly); the abstract copy only for a new one.
                key = str([
                    (x.shape, x.dtype) if isinstance(x, jax.Array) else x
                    for x in jax.tree.leaves((a, kw))
                ])
                seen = self.calls.setdefault(__name, {})
                if key not in seen:
                    seen[key] = jax.tree.map(abstract, (a, kw))
                return __orig(*a, **kw)

            setattr(self.module, name, wrapper)
        return self

    def __exit__(self, *exc):
        for name, orig in self._orig.items():
            setattr(self.module, name, orig)

    def kernels(self) -> dict[str, list[str]]:
        """{program: kernels found in its compiled text} over every
        recorded signature."""
        out: dict[str, list[str]] = {}
        for name, sigs in self.calls.items():
            found: set[str] = set()
            for a, kw in sigs.values():
                text = self._orig[name].lower(*a, **kw).compile().as_text()
                found |= mosaic_kernels(text)
            out[name] = sorted(found)
        return out


def mosaic_kernels(compiled_text: str) -> set[str]:
    """Names of the Mosaic kernels (tpu_custom_call) in a program."""
    found = set()
    for line in compiled_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = re.search(r'op_name="([^"]*)/pallas_call', line)
        scopes = re.findall(r"jit\(([^)]*)\)", m.group(1)) if m else []
        found.add(scopes[-1] if scopes else "pallas_call")
    return found


def _post(base: str, body: dict) -> tuple[int, dict]:
    req = urllib.request.Request(
        base + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=900) as r:
        if not body.get("stream"):
            return r.status, json.loads(r.read())
        text, finish, usage = "", None, None
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            ev = json.loads(line[6:])
            if ev.get("usage"):
                usage = ev["usage"]
            for ch in ev.get("choices", []):
                text += ch["delta"].get("content") or ""
                finish = ch.get("finish_reason") or finish
        return r.status, {
            "choices": [{
                "message": {"content": text}, "finish_reason": finish,
            }],
            "usage": usage,
        }


def smoke_requests(size: Size, seed: int) -> list[dict]:
    from PIL import Image

    rng = np.random.default_rng(seed)
    words = ["oryx", "frame", "video", "token", "patch", "scene", "what"]
    long_text = " ".join(
        words[i] for i in rng.integers(0, len(words), size.long_prompt_chars)
    )[: size.long_prompt_chars]
    img = Image.open(
        os.path.join(ROOT, "assets", "smoke_eval", "media", "img0.png")
    ).convert("RGB").resize((size.image_side, size.image_side))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    url = "data:image/png;base64," + base64.b64encode(buf.getvalue()).decode()

    def body(name, content, **kw):
        return {
            "name": name, "messages": [{"role": "user", "content": content}],
            "max_tokens": size.max_tokens, "temperature": 0.0, **kw,
        }

    return [
        body("short", "Hello, who are you?"),
        body("long", long_text),
        body("stream", "Tell me about TPUs.", stream=True,
             stream_options={"include_usage": True}),
        body("image", [
            {"type": "image_url", "image_url": {"url": url}},
            {"type": "text", "text": "Describe the image."},
        ]),
    ]


def _send_all(base: str, reqs: list[dict]) -> list[dict]:
    out = []
    for r in reqs:
        body = {k: v for k, v in r.items() if k != "name"}
        status, resp = _post(base, body)
        ch = resp["choices"][0]
        ids = reply_ids(ch["message"]["content"])
        usage = resp.get("usage") or {}
        if status != 200 or not ids:
            raise SystemExit(f"serve: {r['name']}: HTTP {status}, ids {ids}")
        n, why = usage.get("completion_tokens"), ch["finish_reason"]
        # Honoured: exactly max_tokens and "length", or an earlier
        # "stop" (EOS / stop string) — never more, never a mislabel.
        honoured = (
            (why == "length" and n == r["max_tokens"] == len(ids))
            or (why == "stop" and n is not None and n <= r["max_tokens"])
        )
        if not honoured:
            raise SystemExit(
                f"serve: {r['name']}: finish_reason {why!r} with "
                f"{n} completion tokens ({len(ids)} ids) for max_tokens "
                f"{r['max_tokens']}"
            )
        out.append({
            "name": r["name"], "ids": ids, "finish_reason": why,
            "prompt_tokens": usage.get("prompt_tokens"),
            "completion_tokens": n,
        })
    return out


def phase_serve(size: Size, params, seed: int, *, ragged: bool,
                on_chip: bool, mesh=None, engine: str = "continuous",
                cfg=None) -> list[dict]:
    """Boot the server the normal way, answer the requests twice, check,
    stop it. Returns the first pass's per-request records."""
    import jax

    from oryx_tpu.analysis.sanitizers import recompile_watchdog
    from oryx_tpu.data import native_loader
    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.serve import api_server
    from oryx_tpu.serve.pipeline import OryxInference

    cfg = cfg or size.cfg
    mode = ("ragged" if ragged else "split") + (
        "" if engine == "continuous" else f"/{engine}"
    )
    pipe = OryxInference(IdTokenizer(), params, cfg, mesh=mesh)
    srv = api_server.build_server(
        pipe, port=0, engine=engine, num_slots=size.num_slots,
        page_size=size.page_size, decode_chunk=size.decode_chunk,
        max_ctx=size.max_ctx, prefill_chunk=size.prefill_chunk,
        ragged=ragged,
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    reqs = smoke_requests(size, seed)
    programs = ("paged_prefill", "paged_decode_chunk", "paged_ragged_step")
    try:
        # Three passes. The first is cold (it compiles, and prefills
        # every prompt whole). The second finds the prompts in the
        # prefix cache, so it runs other paths (page splice, suffix
        # prefill); the third runs exactly what the second ran — same
        # programs, same chip, greedy — and must repeat its token ids
        # with no compile in either.
        with ProgramRecorder(generate_lib, programs) as rec:
            t0 = time.perf_counter()
            first = _send_all(base, reqs)
            t1 = time.perf_counter()
            with recompile_watchdog(budget=10**9, action="record") as wd:
                second = _send_all(base, reqs)
                t2 = time.perf_counter()
                third = _send_all(base, reqs)
        kernels = rec.kernels()
    finally:
        if srv.supervisor is not None:
            srv.supervisor.stop()
        srv.scheduler.close()
        srv.shutdown()
        srv.server_close()
        # Server, handler and scheduler reference each other: collect
        # the cycle now, so that the pool and (on four chips) the
        # sharded weights are not still resident in the next phase.
        del srv, pipe
        gc.collect()
    for a, b in zip(second, third):
        if a["ids"] != b["ids"]:
            raise SystemExit(
                f"serve[{mode}]: {a['name']}: repeated request changed "
                f"token ids {a['ids']} -> {b['ids']}"
            )
    # Information only: a cached prompt is prefilled as a suffix over
    # spliced pages — the same math on another schedule, which bf16
    # near-ties of random weights may or may not survive.
    cold_equals_cached = [r["ids"] for r in first] == [
        r["ids"] for r in second
    ]
    long_chunks = -(-first[1]["prompt_tokens"] // size.prefill_chunk)
    if long_chunks < 2:
        raise SystemExit("serve: the long prompt fits one prefill chunk")
    want = "paged_ragged_step" if ragged else "paged_decode_chunk"
    if want not in kernels:
        raise SystemExit(f"serve[{mode}]: {want} was never dispatched")
    if on_chip and cfg.attn_impl == "pallas" and not all(
        kernels[p] for p in kernels
    ):
        raise SystemExit(
            f"serve[{mode}]: a step program has no tpu_custom_call: "
            f"{kernels}"
        )
    emit(
        f"serve[{mode}]", depth=cfg.llm.num_layers, attn_impl=cfg.attn_impl,
        slots=size.num_slots, page_size=size.page_size,
        max_ctx=size.max_ctx, prefill_chunk=size.prefill_chunk,
        decode_chunk=size.decode_chunk, long_prompt_chunks=long_chunks,
        image_preprocess=(
            "native" if native_loader.is_available() else "numpy"
        ),
        requests=first, step_program_kernels=kernels,
        first_pass_s=round(t1 - t0, 2), repeat_pass_s=round(t2 - t1, 2),
        compile_s_est=round((t1 - t0) - (t2 - t1), 2),
        repeat_pass_compiles=wd.total, repeat_ids_equal=True,
        cold_equals_cached=cold_equals_cached,
        **memory(),
    )
    if wd.total:
        raise SystemExit(
            f"serve[{mode}]: {wd.total} compiles in the repeated passes: "
            f"{wd.counts}"
        )
    return first


def first_logit_row(params, cfg, prompt_ids, *, attn_impl, mesh=None,
                    page_size=64, max_ctx=256):
    """Prefill `prompt_ids` into a private paged pool and return the
    logit row of one decode step (serve/audit.audit_decode_step) — the
    row implementations are compared on."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.models import oryx, qwen2
    from oryx_tpu.parallel import sharding
    from oryx_tpu.serve import audit

    dtype = oryx.compute_dtype(cfg)
    maxp = max_ctx // page_size
    L = len(prompt_ids)
    with sharding.mesh_scope(mesh):
        kv = qwen2.init_paged_kv_cache(cfg.llm, maxp, page_size, dtype=dtype)
        if mesh is not None:
            kv = sharding.shard_paged_kv(kv, mesh)
        embeds = params["llm"]["embed"]["weight"][
            jnp.asarray(prompt_ids, jnp.int32)
        ][None].astype(dtype)
        bt = jnp.arange(maxp, dtype=jnp.int32)[None]
        one = dict(
            temperature=jnp.zeros((1,), jnp.float32),
            top_p=jnp.ones((1,), jnp.float32),
            top_k=jnp.zeros((1,), jnp.int32),
        )
        keys = jax.random.split(jax.random.key(0), 1)
        kv, tok0, keys = generate_lib.paged_prefill(
            params["llm"], cfg.llm, embeds, jnp.asarray([L], jnp.int32), bt,
            kv, jnp.asarray([0], jnp.int32), keys, *one.values(),
            attn_impl=attn_impl, compute_dtype=dtype,
        )
        kv, nxt, row, _ = audit.audit_decode_step(
            params["llm"], cfg.llm, kv, bt, tok0,
            jnp.asarray([L], jnp.int32), keys, **one,
            attn_impl=attn_impl, compute_dtype=dtype,
        )
    return np.asarray(row[0]), int(tok0[0]), int(nxt[0])


def compare_logit_rows(name: str, a, b) -> None:
    (row_a, *toks_a), (row_b, *toks_b) = a, b
    diff = float(np.max(np.abs(row_a - row_b)))
    tol = LOGIT_REL_TOL * max(1.0, float(np.max(np.abs(row_b))))
    ok = bool(np.isfinite(diff) and diff <= tol)
    emit(
        name, logit_max_abs_diff=round(diff, 5), tol=round(tol, 5),
        logit_absmax=round(float(np.max(np.abs(row_b))), 4),
        greedy_ids=[toks_a, toks_b], greedy_agree=toks_a == toks_b,
        vocab=int(row_a.shape[0]), ok=ok,
    )
    if not ok:
        raise SystemExit(f"{name}: logit rows differ by {diff} > {tol}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train_batch(cfg, size: Size, seed: int) -> dict:
    """Seed-made host batch in the recipe's own layout: per microbatch
    `train_rows` rows with one image each and as many text-only rows,
    alternating; `grad_accum_steps` microbatches stacked when the
    recipe accumulates."""
    from oryx_tpu.constants import (
        IGNORE_INDEX, IMAGE_TOKEN_INDEX, MODALITY_IMAGE,
    )
    from oryx_tpu.train import data as data_lib

    rng = np.random.default_rng(seed)
    accum = cfg.train.grad_accum_steps
    V, n = cfg.llm.vocab_size, size.train_text_len
    out = []
    for i in range(accum * 2 * size.train_rows):
        prompt = rng.integers(3, V, size=n // 2)
        answer = rng.integers(3, V, size=n // 2)
        images = []
        if i % 2 == 0:
            prompt = np.concatenate([[IMAGE_TOKEN_INDEX], prompt])
            images = [rng.integers(
                0, 256, (size.image_side, size.image_side, 3), np.uint8
            )]
        ids = np.concatenate([prompt, answer]).astype(np.int64)
        labels = np.full(ids.shape, IGNORE_INDEX, np.int64)
        labels[len(prompt):] = answer
        out.append(data_lib.Example(ids, labels, images, MODALITY_IMAGE))
    kw = dict(
        patch_size=cfg.vision.patch_size, base_grid=cfg.vision.base_grid
    )
    if accum > 1:
        return data_lib.collate_microbatches(out, accum, **kw)
    return data_lib.collate(out, **kw)


def _scratch_dir() -> str:
    """Checkpoints go inside the checkout (gitignored), removed after."""
    d = os.path.join(ROOT, ".smoke_tmp")
    shutil.rmtree(d, ignore_errors=True)
    return d


def train_config(size: Size, *, mesh_kw: dict, depth: int, tune_from=None):
    """The recipe the phase trains: the shipped json (or, in the CPU
    rehearsal, the serve config with LoRA switched on) with its mesh
    overridden and its depth cut — nothing else changed."""
    from oryx_tpu import config as cfg_lib

    if size.train_cfg_path:
        with open(tune_from or size.train_cfg_path) as f:
            cfg = cfg_lib.OryxConfig.from_json(f.read())
    else:
        cfg = dataclasses.replace(size.cfg, train=dataclasses.replace(
            size.cfg.train, tune="lora",
            lora=cfg_lib.LoraConfig(enable=True, r=4, alpha=8.0),
        ))
    return dataclasses.replace(
        cfg,
        llm=dataclasses.replace(cfg.llm, num_layers=depth),
        mesh=cfg_lib.MeshConfig(**mesh_kw),
        train=dataclasses.replace(
            cfg.train, checkpoint_dir=_scratch_dir(), log_every=1,
        ),
    )


def _leaf_sums(tree) -> list[float]:
    import jax
    import jax.numpy as jnp

    return [
        float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
        for x in jax.tree.leaves(tree)
    ]


def _split_lora(llm_layers: dict):
    base = {
        k: {n: w for n, w in v.items() if not n.startswith("lora_")}
        for k, v in llm_layers.items()
    }
    lora = {
        k: {n: w for n, w in v.items() if n.startswith("lora_")}
        for k, v in llm_layers.items()
    }
    return base, lora


def fit_steps(cfg, batch, steps: int, *, params=None, before=None):
    """Trainer(cfg).fit for `steps` steps of `batch`, the way a user
    runs it; checkpoints and the metrics log go to the recipe's
    (scratch) checkpoint_dir, removed afterwards. `before(trainer)`
    runs after construction. Returns (trainer, per-step metric records,
    the compiled step program, fit wall seconds)."""
    from oryx_tpu.train.trainer import Trainer

    os.makedirs(cfg.train.checkpoint_dir, exist_ok=True)
    metrics_path = os.path.join(cfg.train.checkpoint_dir, "metrics.jsonl")
    trainer = Trainer(cfg, params=params, metrics_path=metrics_path)
    del params
    try:
        if before is not None:
            before(trainer)
        compiled = trainer._step.lower(
            trainer.state, trainer._device_batch(batch), cfg=cfg,
            tx=trainer.tx, sharding_mode=trainer.sharding_mode,
            numerics=False,
        ).compile()
        t0 = time.perf_counter()
        trainer.fit(
            iter([batch] * steps), num_steps=steps, resume=False,
            prefetch=0,
        )
        wall = time.perf_counter() - t0
    finally:
        trainer.close()
        trainer.ckpt.close()
    with open(metrics_path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    shutil.rmtree(cfg.train.checkpoint_dir, ignore_errors=True)
    for r in recs:
        r["step_s"] = round(r["dispatch_s"] + r["sync_s"], 3)
    return trainer, recs, compiled, wall


def phase_train(size: Size, params, seed: int, *, on_chip: bool) -> list:
    """Trainer.fit on the LoRA recipe, one device. `params` (the serve
    phase's bf16 weights) are donated to the trainer."""
    import jax
    import jax.numpy as jnp

    # The recipe's mesh overridden to the devices there are: the one
    # chip (the CPU rehearsal has its eight virtual devices).
    cfg = train_config(
        size, mesh_kw=dict(fsdp=jax.device_count()),
        depth=size.cfg.llm.num_layers,
    )
    batch = train_batch(cfg, size, seed)
    # What trains is kept in fp32, as a loaded checkpoint would be
    # (bf16 trainable leaves would also flip their Adam moments to fp32
    # after step 1 and compile the step twice); the frozen base stays
    # bf16.
    params = {**params, "compressor": jax.tree.map(
        lambda x: x.astype(jnp.float32), params["compressor"]
    )}
    sums = {}

    def layer_sums(trainer):
        return [
            _leaf_sums(t)
            for t in _split_lora(trainer.state.params["llm"]["layers"])
        ]

    trainer, recs, compiled, wall = fit_steps(
        cfg, batch, size.train_steps, params=params,
        before=lambda t: sums.update(before=layer_sums(t)),
    )
    del params
    (base_before, lora_before), (base_after, lora_after) = (
        sums["before"], layer_sums(trainer)
    )
    kernels = sorted(mosaic_kernels(compiled.as_text()))
    losses = [r["loss"] for r in recs]
    step_s = [r["step_s"] for r in recs]
    emit(
        "train", recipe=os.path.basename(size.train_cfg_path or "tiny-lora"),
        depth=cfg.llm.num_layers, tune=cfg.train.tune,
        lora_r=cfg.train.lora.r, remat_policy=cfg.train.remat_policy,
        loss_chunk=cfg.train.loss_chunk, attn_impl=cfg.attn_impl,
        batch={k: list(v.shape) for k, v in batch.items()},
        losses=losses, step_seconds=step_s,
        compile_s_est=round(step_s[0] - min(step_s[1:]), 2),
        fit_wall_s=round(wall, 2), step_program_kernels=kernels,
        adapters_moved=lora_before != lora_after,
        base_unchanged=base_before == base_after, **memory(),
    )
    if len(losses) != size.train_steps or not np.all(np.isfinite(losses)):
        raise SystemExit(f"train: losses {losses}")
    if lora_before == lora_after or base_before != base_after:
        raise SystemExit("train: adapters must move and base weights not")
    if on_chip and cfg.attn_impl == "pallas" and not kernels:
        raise SystemExit("train: no tpu_custom_call in the train step")
    return losses


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def run_one_chip(size: Size, seed: int, *, on_chip: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import oryx
    from oryx_tpu.utils import flops

    dev = jax.devices()[0]
    emit(
        "start", device_kind=dev.device_kind, seed=seed,
        depth_note=size.depth_note,
        disk_free_gb=round(shutil.disk_usage(ROOT).free / 1e9, 1),
        peak_bf16_flops=flops.chip_peak_flops(dev.device_kind),
        jax=jax.__version__,
    )
    phase_kernels(size)

    t0 = time.perf_counter()
    params = oryx.init_params(
        size.cfg, jax.random.key(seed), dtype=jnp.bfloat16
    )
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    emit(
        "init", params=int(n_params), dtype="bfloat16",
        seconds=round(time.perf_counter() - t0, 2), **memory(),
    )

    prompt = IdTokenizer().encode("The quick brown fox jumps over the dog.")
    rows = {
        impl: first_logit_row(
            params, size.cfg, prompt, attn_impl=impl,
            page_size=size.page_size, max_ctx=4 * size.page_size,
        )
        for impl in ("pallas", "xla")
    }
    compare_logit_rows("serve/pallas_vs_xla", rows["pallas"], rows["xla"])

    ids = {}
    for ragged in (False, True):
        first = phase_serve(size, params, seed, ragged=ragged,
                            on_chip=on_chip)
        ids["ragged" if ragged else "split"] = [r["ids"] for r in first]
    emit(
        "serve/split_vs_ragged",
        greedy_agree=ids["split"] == ids["ragged"],
        note="information only: two step programs, near-flat logits",
    )
    losses = phase_train(size, params, seed, on_chip=on_chip)
    return {"token_ids": ids, "train_losses": losses}


# ---------------------------------------------------------------------------
# four chips (--chips 4): the fsdp=4 trainer and the tp=4 sharded engine
# ---------------------------------------------------------------------------

# The chip's compiler refuses a Pallas kernel under a mesh ("Mosaic
# kernels cannot be automatically partitioned. Please wrap the call in a
# shard_map") and no shard_map wraps the flash or paged kernels, so both
# four-chip paths run the XLA attention. Found by the chipless compile
# rehearsal (PR 21); ROADMAP S7/S8/D4 carry it.
FOUR_CHIP_ATTN = "xla"


def fsdp4_config(size: Size):
    """The shipped full-tune recipe with its mesh overridden to fsdp=4,
    its depth cut and the attention the compiler accepts under a mesh."""
    cfg = train_config(
        size, mesh_kw=dict(fsdp=4), depth=FSDP4_DEPTH,
        tune_from=os.path.join(
            ROOT, "scripts", "configs", "oryx_7b_sft.json"
        ) if size.train_cfg_path else None,
    )
    cfg = dataclasses.replace(cfg, attn_impl=FOUR_CHIP_ATTN)
    if not size.train_cfg_path:
        # CPU rehearsal: full tune, no adapters, and microbatches
        # stacked as the shipped recipe's grad accumulation stacks them.
        cfg = dataclasses.replace(cfg, train=dataclasses.replace(
            cfg.train, tune="full", lora=type(cfg.train.lora)(),
            grad_accum_steps=2,
        ))
    return cfg


def phase_fsdp4(size: Size, seed: int) -> list:
    """The shipped full-tune recipe on an fsdp=4 mesh, depth cut: three
    Trainer.fit steps; step-0 loss against a forward-only loss of the
    same weights and batch on device 0."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import oryx
    from oryx_tpu.train import step as step_lib

    cfg = fsdp4_config(size)
    batch = train_batch(cfg, size, seed)
    # Reference first, while device 0 is empty: the trainer's fp32
    # weights cast to bf16 are exactly init_params(dtype=bf16) of the
    # same key, which is what the step computes with.
    dev0 = jax.devices()[0]
    with jax.default_device(dev0):
        ref_params = oryx.init_params(
            cfg, jax.random.key(cfg.train.seed), dtype=jnp.bfloat16
        )
        loss_fn = jax.jit(step_lib.microbatch_loss, static_argnums=(1,))
        accum = cfg.train.grad_accum_steps
        stacked = batch if accum > 1 else {
            k: v[None] for k, v in batch.items()
        }
        # The step reports the mean of its microbatches' losses.
        ref_loss = float(np.mean([
            float(loss_fn(
                ref_params, cfg,
                {k: jnp.asarray(v[i]) for k, v in stacked.items()},
            )[0])
            for i in range(accum)
        ]))
    del ref_params
    mem_ref = memory()

    mem = {}
    trainer, recs, compiled, wall = fit_steps(
        cfg, batch, size.train_steps,
        before=lambda t: mem.update(init=memory()),
    )
    mem_init, analysis = mem["init"], compiled.memory_analysis()
    placement = sorted({
        str(x.sharding.spec) for x in jax.tree.leaves(trainer.state.params)
    })
    losses = [r["loss"] for r in recs]
    n_params = sum(
        x.size for x in jax.tree.leaves(trainer.state.params)
    )
    tol = 1e-2 * abs(ref_loss)
    ok = (
        len(losses) == size.train_steps and bool(np.all(np.isfinite(losses)))
        and abs(losses[0] - ref_loss) <= tol
    )
    emit(
        "fsdp4_train", recipe="oryx_7b_sft.json", depth=cfg.llm.num_layers,
        params=int(n_params), tune=cfg.train.tune,
        grad_accum_steps=cfg.train.grad_accum_steps,
        attn_impl=cfg.attn_impl,
        attn_note="the compiler refuses a Mosaic kernel under a mesh; "
        "XLA attention ran",
        batch={k: list(v.shape) for k, v in batch.items()},
        losses=losses, ref_loss_device0=ref_loss,
        step0_abs_diff=round(abs(losses[0] - ref_loss), 6),
        tol=round(tol, 6),
        step_seconds=[r["step_s"] for r in recs],
        fit_wall_s=round(wall, 2),
        step_program_memory_per_device={
            "argument_bytes": analysis.argument_size_in_bytes,
            "output_bytes": analysis.output_size_in_bytes,
            "alias_bytes": analysis.alias_size_in_bytes,
            "temp_bytes": analysis.temp_size_in_bytes,
        },
        param_specs=placement, memory_after_reference=mem_ref,
        memory_after_trainer_init=mem_init, **memory(), ok=ok,
    )
    if not ok:
        raise SystemExit(
            f"fsdp4_train: losses {losses} against reference {ref_loss}"
        )
    return losses


def phase_tp4(size: Size, seed: int) -> list:
    """`--engine sharded` on a tp=4 mesh at the serve phase's depth: the
    same requests, and its first-token logit row against the one-chip
    engine's on device 0."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx
    from oryx_tpu.parallel import mesh as mesh_lib
    from oryx_tpu.parallel.sharding import shard_params
    from oryx_tpu.serve.builder import serving_param_shardings

    cfg = dataclasses.replace(size.cfg, attn_impl=FOUR_CHIP_ATTN)
    mesh = mesh_lib.build_mesh(cfg_lib.MeshConfig(tp=4))
    dev0 = jax.devices()[0]
    prompt = IdTokenizer().encode("The quick brown fox jumps over the dog.")
    with jax.default_device(dev0):
        params = oryx.init_params(
            cfg, jax.random.key(seed), dtype=jnp.bfloat16
        )
        one = first_logit_row(
            params, cfg, prompt, attn_impl=cfg.attn_impl,
            page_size=size.page_size, max_ctx=4 * size.page_size,
        )
    sharded = shard_params(
        params, serving_param_shardings(mesh, params, "tp")
    )
    del params
    four = first_logit_row(
        sharded, cfg, prompt, attn_impl=cfg.attn_impl, mesh=mesh,
        page_size=size.page_size, max_ctx=4 * size.page_size,
    )
    compare_logit_rows("tp4/four_chips_vs_one", four, one)
    first = phase_serve(
        size, sharded, seed, ragged=False, on_chip=True, mesh=mesh,
        engine="sharded", cfg=cfg,
    )
    return [r["ids"] for r in first]


def run_four_chips(size: Size, seed: int) -> dict:
    import jax

    emit(
        "start", device_kind=jax.devices()[0].device_kind, seed=seed,
        chips=len(jax.devices()), fsdp4_depth=FSDP4_DEPTH,
        tp4_depth=size.cfg.llm.num_layers,
        depth_note=(
            f"fsdp=4 full tune: fp32 weights + AdamW moments + grads are "
            f"16 B/param, so depth {FSDP4_DEPTH} (2.02 B decoder + 0.43 B "
            "vision params = 9.8 GB of state per chip) is what four "
            "16 GB chips hold beside activations"
        ),
    )
    ids = phase_tp4(size, seed)
    losses = phase_fsdp4(size, seed)
    return {"fsdp4_losses": losses, "tp4_token_ids": ids}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--record", default=None,
        help="write token ids and train losses here as JSON (to compare "
        "a cold run with a warm one)",
    )
    args = ap.parse_args(argv)

    import jax

    from oryx_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    devs = jax.devices()
    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if devs[0].platform != "tpu" or len(devs) != args.chips:
        print(json.dumps({
            "ok": False, "device": device,
            "error": f"needs {args.chips} TPU chip(s)",
        }))
        return 1
    t0 = time.perf_counter()
    emit("cache", dir=cache_dir, entries_at_start=(
        len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    ))
    if args.chips == 4:
        record = run_four_chips(chip_size(), args.seed)
    else:
        record = run_one_chip(chip_size(), args.seed, on_chip=True)
    emit("done", wall_s=round(time.perf_counter() - t0, 1))
    if args.record:
        os.makedirs(os.path.dirname(os.path.abspath(args.record)),
                    exist_ok=True)
        with open(args.record, "w") as f:
            json.dump(record, f)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
