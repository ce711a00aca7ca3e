"""Pallas kernel validation: parity of every main-path kernel against
its XLA reference, plus a forward/backward timing probe.

    python scripts/tpu_validate.py            # on the chip (or CPU interpret)
    python scripts/tpu_validate.py --fast     # parity only, no timings

The kernel cases run at Oryx-7B geometry (decoder Hq28 Hk4 D128, OryxViT
H16 D72, page 64) at the default --seq (2048 on TPU): causal GQA flash
forward + backward, segment-packed ViT attention, KV-cache decode
layout, and the paged kernels — paged decode and the packed ragged
kernel over a bf16 and an int8 pool. chip_smoke.py calls
`parity_cases` in-process for its kernels phase. The CLI also certifies
the END-TO-END dense decode on a tiny model (prefill kernel + cached
decode under the early-exit while_loop, and the split-prefill
prefix-cache path) by greedy token streams: those lines carry
`prefix_agreement` (mean first-divergence fraction; 1.0 = bitwise)
instead of `max_abs_diff`. Every line has `"pass"`; the script EXITS
NONZERO if any case fails, so a CPU run (interpret mode; small --seq
shrinks every case) fails on regressions too. Times come only from a
chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# Oryx-7B attention geometry (config.LLMConfig / VisionConfig defaults).
HQ, HK, D = 28, 4, 128
VIT_H, VIT_D = 16, 72
PAGE = 64


def _qkv(jax, jnp, key, B, Tq, Tk, Hq, Hk, D, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (B, Tq, Hq, D), dtype),
        jax.random.normal(kk, (B, Tk, Hk, D), dtype),
        jax.random.normal(kv, (B, Tk, Hk, D), dtype),
    )


def parity_cases(seq: int, *, e2e: bool = True, page_size: int = PAGE):
    """Run every case at sequence length `seq`; prints one JSON line per
    case and returns (all_passed, records). bf16 with bf16 bounds on a
    TPU, fp32 with tight bounds in interpret mode elsewhere."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import paged_kv
    from oryx_tpu.ops.attention import attention as xla_attention
    from oryx_tpu.ops.pallas import paged_attention as ppa
    from oryx_tpu.ops.pallas.flash_attention import flash_attention
    from oryx_tpu.ops.pallas.segment_attention import segment_attention

    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    # Outputs are O(1): a bf16 forward lands within ~2 ulp (3e-2).
    # Gradients sum over the 7 q heads of a kv group and over rows, so
    # their size grows with the shape: the backward bound is 2% of the
    # reference's largest element (5 bf16 ulp; kernel and reference
    # both round in bf16) with the absolute bound as floor.
    fwd_tol = 3e-2 if on_tpu else 1e-3
    bwd_tol = 5e-1 if on_tpu else 1e-3
    bwd_rel = 2e-2 if on_tpu else 0.0
    T = seq
    records: list[dict] = []

    def record(name, got, ref, tol, rel=0.0, **extra):
        ref = ref.astype(jnp.float32)
        diff = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
        ref_absmax = float(jnp.max(jnp.abs(ref)))
        tol = max(tol, rel * ref_absmax)
        rec = {
            "case": name, "max_abs_diff": round(diff, 6),
            "tol": round(tol, 6), "ref_absmax": round(ref_absmax, 4),
            "pass": bool(np.isfinite(diff) and diff <= tol), **extra,
        }
        records.append(rec)
        print(json.dumps(rec), flush=True)

    # 1. Causal GQA prefill forward + backward (B1 T<seq> Hq28 Hk4 D128).
    q, k, v = _qkv(jax, jnp, jax.random.key(0), 1, T, T, HQ, HK, D, dtype)
    shape = [1, T, HQ, HK, D]
    record(
        "flash_causal_gqa_fwd",
        flash_attention(q, k, v, causal=True),
        xla_attention(q, k, v, causal=True),
        fwd_tol, shape=shape,
    )

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True).astype(jnp.float32) ** 2
        )

    gp = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gp, gx):
        record(
            f"flash_causal_gqa_bwd_{name}", a, b, bwd_tol, rel=bwd_rel,
            shape=shape,
        )

    # 2. Segment-packed OryxViT attention: B1 T<2*seq> H16 D72, uneven
    #    segments (head_dim 72 is the ViT's, not a multiple of 128).
    P = max(2 * T, 16)
    seg = np.zeros(P, np.int32)
    bounds = [0, P // 5, P // 2, (3 * P) // 4, P]
    for s, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:]), start=1):
        seg[lo:hi] = s
    seg = jnp.asarray(seg)[None]
    q2, k2, v2 = _qkv(
        jax, jnp, jax.random.key(1), 1, P, P, VIT_H, VIT_H, VIT_D, dtype
    )
    record(
        "segment_vit_d72_fwd",
        segment_attention(q2, k2, v2, seg, seg),
        xla_attention(q2, k2, v2, causal=False,
                      q_segment_ids=seg, kv_segment_ids=seg),
        fwd_tol, shape=[1, P, VIT_H, VIT_D],
    )

    # 3. KV-cache decode layout: B4 Tq8 S<2*seq>, arbitrary q positions
    #    (the regression case for the causal DMA-clamp fix).
    S = 2 * T
    base = jnp.asarray([T - 9, T + 3, 5 + 7, S - 9], jnp.int32)
    qpos = base[:, None] + jnp.arange(8, dtype=jnp.int32)[None, :]
    q3, k3, v3 = _qkv(jax, jnp, jax.random.key(2), 4, 8, S, HQ, HK, D, dtype)
    kv_mask = (
        jnp.arange(S)[None, :] <= qpos[:, -1:]
    ).astype(jnp.int32)
    kw = dict(causal=True, q_positions=qpos, kv_positions=None,
              kv_mask=kv_mask)
    record(
        "flash_kv_cache_decode_fwd",
        flash_attention(q3, k3, v3, **kw),
        xla_attention(q3, k3, v3, **kw),
        fwd_tol, shape=[4, 8, S, HQ, HK, D],
    )

    # 4. Paged kernels: a pool of pages of `page_size`, four sequences
    #    of uneven length (one ending on a page edge, one of a single
    #    token) addressed through block tables; bf16 and int8 pools.
    ps = page_size
    lens = np.asarray([T, T - ps // 2 - 1, ps, 1], np.int32)
    maxp = -(-T // ps)
    n_seq = len(lens)
    P_pool = n_seq * maxp + 1
    rng = np.random.default_rng(3)
    bt = rng.permutation(n_seq * maxp).astype(np.int32).reshape(n_seq, maxp)
    bt = jnp.asarray(bt)
    kk, kv_, kq, kr = jax.random.split(jax.random.key(4), 4)
    new_k = jax.random.normal(kk, (n_seq, maxp * ps, HK, D), dtype)
    new_v = jax.random.normal(kv_, (n_seq, maxp * ps, HK, D), dtype)
    zero = jnp.zeros((n_seq,), jnp.int32)
    pools = {
        "bf16": lambda: jnp.zeros((P_pool, ps, HK, D), dtype),
        "int8": lambda: paged_kv.QuantPages(
            jnp.zeros((P_pool, ps, HK, D), jnp.int8),
            jnp.zeros((P_pool, ps), jnp.float32), dtype,
        ),
    }
    q_dec = jax.random.normal(kq, (n_seq, 1, HQ, D), dtype)
    # Packed rows: one decode lane per sequence plus a prefill suffix
    # of sequence 0 (consecutive positions ending at its last token).
    sfx = min(8, T)
    r_seg = np.concatenate([np.arange(n_seq), np.zeros(sfx)]).astype(np.int32)
    r_pos = np.concatenate([lens - 1, T - sfx + np.arange(sfx)]).astype(np.int32)
    q_rag = jax.random.normal(kr, (len(r_seg), HQ, D), dtype)
    for name, make in pools.items():
        k_pool = paged_kv.write_pages(make(), new_k, bt, zero)
        v_pool = paged_kv.write_pages(make(), new_v, bt, zero)
        geom = dict(pool=name, page_size=ps, lengths=lens.tolist(),
                    heads=[HQ, HK, D])
        record(
            f"paged_decode_{name}",
            ppa.ragged_decode_attention(
                q_dec, k_pool, v_pool, bt, jnp.asarray(lens)
            ),
            paged_kv.ragged_decode_attention(
                q_dec, k_pool, v_pool, bt, jnp.asarray(lens)
            ),
            fwd_tol, **geom,
        )
        record(
            f"paged_ragged_{name}",
            ppa.ragged_paged_attention(
                q_rag, k_pool, v_pool, bt,
                jnp.asarray(r_seg), jnp.asarray(r_pos),
            ),
            paged_kv.ragged_paged_attention(
                q_rag, k_pool, v_pool, bt,
                jnp.asarray(r_seg), jnp.asarray(r_pos),
            ),
            fwd_tol, rows=len(r_seg), **geom,
        )

    if e2e:
        records.extend(_e2e_decode_cases(T, dtype))
    return all(r["pass"] for r in records), records


def _e2e_decode_cases(T: int, dtype) -> list[dict]:
    import jax
    import jax.numpy as jnp

    records: list[dict] = []
    # END-TO-END dense decode certification on a tiny model: greedy
    #    generate() — prefill kernel + cached decode under the early-exit
    #    while_loop — Pallas vs XLA token agreement, plus split-prefill
    #    (the ChatSession prefix-cache path: prefill a prefix into the
    #    cache, continue with a suffix at start>0) vs one-shot generate,
    #    which must agree with itself per impl.
    from oryx_tpu.config import GenerationConfig, LLMConfig
    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.models import qwen2

    lcfg = LLMConfig(
        vocab_size=2048, hidden_size=256, intermediate_size=512,
        num_layers=2, num_heads=8, num_kv_heads=2, head_dim=64,
        attention_bias=True,
    )
    gcfg = GenerationConfig(temperature=0.0, eos_token_id=10**9)
    lp = qwen2.init_params(lcfg, jax.random.key(3), dtype=jnp.float32)
    # Scales with --seq so small smoke runs stay small (floor keeps
    # half > the 5-token length stagger below).
    Tp = max(T // 8, 16)
    emb_key = jax.random.key(4)
    embeds = jax.random.normal(emb_key, (2, Tp, 256), dtype) * 0.2
    lengths = jnp.asarray([Tp, Tp - 5], jnp.int32)
    cache_len = 2 * Tp

    def gen(impl, kv_cache=None, start=None, embeds_=None, lengths_=None):
        toks, num, fin = generate_lib.generate(
            lp, lcfg, gcfg,
            inputs_embeds=embeds_ if embeds_ is not None else embeds,
            lengths=lengths_ if lengths_ is not None else lengths,
            max_new_tokens=16, cache_len=cache_len,
            attn_impl=impl, compute_dtype=dtype,
            kv_cache=kv_cache, start=start,
        )
        return np.asarray(toks)

    def record_agreement(name, a, b, min_frac):
        """Greedy decode is autoregressive: ONE near-tie argmax flip
        diverges every later token, so raw agreement is misleading.
        Score the FIRST-DIVERGENCE point instead: mean over rows of
        (first mismatching step / steps), 1.0 = bitwise identical."""
        steps = a.shape[1]
        fracs = []
        for ra, rb in zip(a, b):
            neq = ra != rb
            fracs.append(
                (int(np.argmax(neq)) if neq.any() else steps) / steps
            )
        frac = float(np.mean(fracs))
        rec = {
            "case": name, "prefix_agreement": round(frac, 4),
            "min": min_frac, "pass": frac >= min_frac,
        }
        records.append(rec)
        print(json.dumps(rec), flush=True)

    impls = ("pallas", "xla")  # pallas interprets on CPU like cases 1-3
    toks_by_impl = {i: gen(i) for i in impls}
    # bf16 kernel-vs-XLA near-ties can flip a greedy argmax mid-stream;
    # demand the first flip lands in the back half of the window.
    record_agreement(
        "generate_pallas_vs_xla",
        toks_by_impl["pallas"], toks_by_impl["xla"], 0.5,
    )
    for impl in impls:
        # Split prefill: rows share a Tp//2 prefix; continue with the
        # remaining embeds at start=Tp//2. Same math, different
        # schedule — tokens must match the one-shot run per impl.
        half = Tp // 2
        cache = qwen2.init_kv_cache(lcfg, 2, cache_len, dtype=dtype)
        _, _, _, cache = generate_lib.generate(
            lp, lcfg, gcfg, inputs_embeds=embeds[:, :half],
            lengths=jnp.asarray([half, half], jnp.int32),
            max_new_tokens=1, cache_len=cache_len, attn_impl=impl,
            compute_dtype=dtype, kv_cache=cache,
            start=jnp.asarray(0, jnp.int32), return_cache=True,
        )
        split = gen(
            impl, kv_cache=cache, start=jnp.asarray(half, jnp.int32),
            embeds_=embeds[:, half:], lengths_=lengths,
        )
        # Same math, different fp reduction schedule — a near-tie flip
        # is legal even off-TPU, so bitwise identity is not demanded.
        record_agreement(
            f"split_prefill_{impl}", split, toks_by_impl[impl], 0.75,
        )
    return records


def timing_probe(args):
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops.attention import attention as xla_attention
    from oryx_tpu.ops.pallas.flash_attention import flash_attention

    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    q, k, v = _qkv(
        jax, jnp, jax.random.key(3), args.batch, args.timing_seq,
        args.timing_seq, HQ, HK, D, dtype,
    )

    def timed(fn, reps):
        fn(q, k, v).block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(q, k, v)
        out.block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e3

    for name, attn in (("flash", flash_attention), ("xla", xla_attention)):
        def fwd(q, k, v, attn=attn):
            return jnp.sum(attn(q, k, v, causal=True).astype(jnp.float32))

        def fwdbwd(q, k, v, attn=attn):
            grads = jax.grad(
                lambda *a: jnp.sum(
                    attn(*a, causal=True).astype(jnp.float32) ** 2
                ),
                argnums=(0, 1, 2),
            )(q, k, v)
            return sum(jnp.sum(g.astype(jnp.float32)) for g in grads)

        print(json.dumps({
            "timing": name,
            "fwd_ms": round(timed(jax.jit(fwd), args.reps), 2),
            "fwdbwd_ms": round(timed(jax.jit(fwdbwd), args.reps), 2),
            "shape": [args.batch, args.timing_seq, HQ, HK, D],
        }))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", type=int, default=None,
                    help="parity sequence length (default: 2048 on TPU, "
                    "128 on CPU)")
    ap.add_argument("--timing-seq", type=int, default=4096,
                    help="timing-probe sequence length")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--fast", action="store_true", help="parity only")
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    if args.seq is None:
        args.seq = 2048 if backend == "tpu" else 128
    print(json.dumps({
        "backend": backend,
        "device": jax.devices()[0].device_kind,
        "seq": args.seq,
    }))
    ok, _ = parity_cases(args.seq)
    if not args.fast and backend == "tpu":
        timing_probe(args)
    if not ok:
        raise SystemExit("kernel parity FAILED (see cases above)")


if __name__ == "__main__":
    main()
