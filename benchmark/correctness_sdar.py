"""The comparison that decides `correct` in a block-diffusion cell.

Several seeded prompts of different lengths and tails (254 tokens, of
which 252 are prefilled and 2 open the first block, and three short
ones with tails of 3, 1 and 0) go through the served path TOGETHER, as
live slots of one paged pool: `paged_prefill` in the configuration's
chunks, then `blocks` blocks. Every block is generated twice:

  - by `paged_block_step`, the timed program, all slots in one dispatch
    (its on-device loop, its unmasking, its commit);
  - forward by forward, by `paged_block_step`'s own forward
    (`paged_block_forward`: the same packed lanes of every slot, the
    (segment, position) ragged path, the configuration's attn_impl and
    dtype), with the program's own unmasking rule applied on the host.

At EVERY denoising forward of every slot the plain reference
(reference/sdar_moe_ref.py) is given the same ids, the slot's prompt,
its committed blocks and the block as it stands, mask ids included, and
the B rows of logits are compared; every block after the first also
proves the commit forwards before it.

**Routing is discontinuous**, so the reference runs twice. The 8th and
9th largest router probability of a token can lie closer than bf16
rounding of the hidden state moves them (at the published widths one
top-8 set in ten differs), and then program and reference send the
token to different experts: a different function, not a rounding of
the same one. The FREE run (the reference routes by itself) therefore
measures flips, not precision: int8 activations in the expert products
read the same there as bf16. The FORCED run gives the reference the
program's top-K INDICES (never its weights, hidden states or logits:
the reference still weighs the forced experts by its own router's
probabilities) and so compares the same function in two precisions.

What decides, all of it:

  1. `forced_logit_rms_diff`, the root mean square over every compared
     logit (rows x vocabulary, 14 million values) of program minus
     forced reference, <= FORCED_RMS_REL_TOL of the reference's own
     root mean square: the precision limit. Its two readings at the
     published widths on the chip (PERF.md section 6, PR 26): bf16 as
     served 0.605-0.645 % over 23 seeds; the same program with int8
     activations in the grouped products 0.821-0.831 % over four, which
     must fail and does; the limit, 0.72 %, is the geometric mean of
     the first ten and those four, 12 % over bf16's largest and 14 %
     under int8's smallest where a seed moves a reading by 2 %. The
     LARGEST single difference does not tell the two apart: it is an
     extreme of those 14 million values and its readings touch (bf16
     0.63-0.80 % of the largest |logit|, int8 0.84-0.90 %); it is held
     to the gross FORCED_MAX_REL_TOL, the 5 % that benchmark/
     correctness.py holds the dense decoder to, for a fault in a few
     logits that a mean hides.
  2. `routing_agree`, the share of (row, layer) top-K SETS on which
     the free run and the program agree, >= ROUTING_AGREE_MIN. The
     forced run cannot see a program that routes wrongly (top-7 reads
     0 here; forced to its own sets it would pass 1.); flips under bf16
     leave 0.88-0.92 (int8 activations 0.88-0.89: no precision limit).
  3. In every slot, the timed program's new tokens equal the
     forward-by-forward ones on >= STEP_AGREE_MIN of the positions. The
     two run the same forward, so a sound program reads 1.0 (a near-tie
     in confidence may reorder an unmasking inside one block of one
     slot); a fault in the on-device loop, the commit or the routing of
     packed lanes to slots' pages leaves a slot with other tokens.

The free run's differences are reported (`logit_max_abs_diff` 2.4-4.4 %
of the largest |logit|, `logit_rms_diff` 1.2-1.7 % of the root mean
square) and decide nothing: they measure how far a flipped expert moves
a logit, which bf16 and int8 do alike.
"""

from __future__ import annotations

import numpy as np

FORCED_RMS_REL_TOL = 7.2e-3
FORCED_MAX_REL_TOL = 5e-2
ROUTING_AGREE_MIN = 0.8
STEP_AGREE_MIN = 0.5


def _programs():
    from oryx_tpu.models import generate as g

    return g.paged_prefill, g.paged_block_forward, g.paged_block_step


def block_logit_check(params, cfg, seed: int, *, page_size: int,
                      prefill_chunk: int, prompt_tokens: int = 254,
                      extra_prompt_tokens=(35, 17, 8), blocks: int = 3,
                      program=None, programs=None) -> dict:
    """params/cfg: what the reference computes with (the llm subtree
    and OryxConfig). program: (llm params, OryxConfig) the served path
    runs with, default the same (the tests' mutations differ here).
    programs: (paged_prefill, paged_block_forward, paged_block_step),
    default the jitted ones."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.models import oryx, qwen2

    from benchmark.reference import sdar_moe_ref as ref

    llm = cfg.llm
    p_params, p_cfg = program or (params, cfg)
    prefill, forward, step = programs or _programs()
    B, mask_id = llm.block_length, llm.mask_token_id
    gen = p_cfg.generation
    steps = gen.denoising_steps or B
    max_steps = steps if gen.remasking == "low_confidence_static" else B
    dtype = oryx.compute_dtype(p_cfg)
    common = dict(attn_impl=p_cfg.attn_impl, compute_dtype=dtype)
    rule = dict(steps=steps, remasking=gen.remasking,
                threshold=gen.confidence_threshold)

    rng = np.random.default_rng(seed)
    hi = min(llm.vocab_size, mask_id) if mask_id > 3 else llm.vocab_size
    prompts = [rng.integers(3, hi, n).astype(np.int32)
               for n in (prompt_tokens, *extra_prompt_tokens)]
    S = len(prompts)
    heads = [len(p) - len(p) % B for p in prompts]
    maxp = -(-(max(heads) + (blocks + 1) * B) // page_size)
    kv = qwen2.init_paged_kv_cache(p_cfg.llm, S * maxp, page_size,
                                   dtype=dtype)
    bt = jnp.arange(S * maxp, dtype=jnp.int32).reshape(S, maxp)
    one = (jnp.zeros((1,), jnp.float32), jnp.ones((1,), jnp.float32),
           jnp.zeros((1,), jnp.int32))
    greedy = (jnp.zeros((S,), jnp.float32), jnp.ones((S,), jnp.float32),
              jnp.zeros((S,), jnp.int32))
    keys1 = jax.random.split(jax.random.key(0), 1)
    keys = jax.random.split(jax.random.key(0), S)
    live = jnp.ones((S,), bool)

    # Every prompt's whole blocks, in the configuration's prefill chunks,
    # each into its own pages of the one pool.
    routed = [[] for _ in range(S)]  # the program's expert ids, [L, rows, K]
    for s, (ids, head) in enumerate(zip(prompts, heads)):
        emb = p_params["embed"]["weight"][jnp.asarray(ids[:head])][None]
        emb = generate_lib.pad_embeds_for_chunks(emb.astype(dtype),
                                                 prefill_chunk)
        for off in range(0, head, prefill_chunk):
            end = min(off + prefill_chunk, head)
            kv, _, keys1, routing = prefill(
                p_params, p_cfg.llm,
                generate_lib.slice_embeds(emb, jnp.asarray(off, jnp.int32),
                                          width=prefill_chunk),
                jnp.asarray([end], jnp.int32), bt[s:s + 1], kv,
                jnp.asarray([off], jnp.int32), keys1, *one,
                return_routing=True, **common,
            )
            routed[s].append(np.asarray(routing["ids"])[:, : end - off])

    seqs = [[int(t) for t in ids[:head]] for ids, head in zip(prompts, heads)]
    known = [[int(t) for t in ids[head:]] for ids, head in zip(prompts, heads)]
    step_agree, step_toks = np.zeros(S, int), np.zeros(S, int)
    pending = []  # (slot, ids given to the reference, program logits, ids)
    for _ in range(blocks):
        lengths = jnp.asarray([len(q) for q in seqs], jnp.int32)
        n_known = np.asarray([len(k) for k in known], np.int32)
        # The timed program on this block first. It writes only this
        # block's positions, which the forward-by-forward pass below
        # writes again.
        blk = np.full((S, B), mask_id, np.int32)
        for s, k in enumerate(known):
            blk[s, : len(k)] = k
        kv, step_tokens, _, _, _, keys, _ = step(
            p_params, p_cfg.llm, kv, bt, jnp.asarray(blk),
            jnp.asarray(n_known), lengths, jnp.zeros((S,), bool), keys,
            *greedy, eos=-1, **rule, **common,
        )
        masked = np.arange(B)[None, :] >= n_known[:, None]
        for t in range(max_steps):
            if not masked.any():
                break
            lg, kv, routing = forward(
                p_params, p_cfg.llm, kv, bt, jnp.asarray(blk), lengths,
                live, **common,
            )
            lg = np.asarray(lg, np.float32).reshape(S, B, -1)
            cur = np.asarray(routing["ids"])
            cur = cur.reshape(cur.shape[0], S, B, -1)
            for s in np.flatnonzero(masked.any(-1)):
                pending.append((s, seqs[s] + [int(x) for x in blk[s]],
                                lg[s], cur[:, s]))
            x0 = lg.argmax(-1)
            lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1))
            conf = np.exp(-lse)  # softmax probability of the argmax
            fix = np.asarray(generate_lib.block_unmask(
                jnp.asarray(masked), jnp.asarray(conf),
                jnp.asarray(t, jnp.int32), **rule,
            ))
            blk = np.where(fix, x0, blk).astype(np.int32)
            masked = masked & ~fix
        _, kv, routing = forward(
            p_params, p_cfg.llm, kv, bt, jnp.asarray(blk), lengths, live,
            **common,
        )
        cur = np.asarray(routing["ids"])
        cur = cur.reshape(cur.shape[0], S, B, -1)
        got = np.asarray(step_tokens)
        for s in range(S):
            routed[s].append(cur[:, s])
            new = slice(int(n_known[s]), B)
            step_agree[s] += int(np.sum(got[s][new] == blk[s][new]))
            step_toks[s] += B - int(n_known[s])
            seqs[s] += [int(x) for x in blk[s]]
        known = [[] for _ in range(S)]

    # The reference, forward by forward: free, then with the program's
    # expert ids forced.
    committed = [np.concatenate(r, axis=1) for r in routed]  # [L, len, K]
    worst = {"free": 0.0, "forced": 0.0}
    absmax, agree, sets, compared, argmax_agree = 0.0, 0, 0, 0, 0
    sq = {"forced": 0.0, "free": 0.0, "ref": 0.0}  # sums of squares
    for s, given, lg, cur in pending:
        base = len(given) - B
        rows = list(range(base, base + B))
        prog = np.concatenate([committed[s][:, :base], cur], axis=1)
        want, chosen = ref.logits(params, llm, given, rows=rows,
                                  return_experts=True)
        want = np.asarray(want)
        absmax = max(absmax, float(np.max(np.abs(want))))
        same = np.all(np.sort(prog, -1) == np.sort(chosen, -1), axis=-1)
        agree += int(same.sum())
        sets += same.size
        worst["free"] = max(worst["free"], float(np.max(np.abs(lg - want))))
        sq["free"] += float(np.sum(np.square(lg - want, dtype=np.float64)))
        sq["ref"] += float(np.sum(np.square(want, dtype=np.float64)))
        argmax_agree += int(np.sum(lg.argmax(-1) == want.argmax(-1)))
        compared += B
        want = np.asarray(ref.logits(params, llm, given, rows=rows,
                                     forced_experts=prog))
        worst["forced"] = max(worst["forced"],
                              float(np.max(np.abs(lg - want))))
        sq["forced"] += float(np.sum(np.square(lg - want, dtype=np.float64)))
    rms = {k: float(np.sqrt(v / max(1, compared * lg.shape[-1])))
           for k, v in sq.items()}
    routing_agree = agree / max(1, sets)
    step_share = step_agree / np.maximum(1, step_toks)
    passed = {
        "forced": bool(np.isfinite(rms["forced"])
                       and rms["forced"] <= FORCED_RMS_REL_TOL * rms["ref"]
                       and worst["forced"] <= FORCED_MAX_REL_TOL * absmax),
        "routing": routing_agree >= ROUTING_AGREE_MIN,
        "step_tokens": bool(np.all(step_share >= STEP_AGREE_MIN)),
    }
    return {
        "ok": all(passed.values()), "passed": passed,
        "forced_logit_rms_diff": rms["forced"],
        "forced_tol": FORCED_RMS_REL_TOL * rms["ref"],
        "forced_logit_max_abs_diff": worst["forced"],
        "forced_max_tol": FORCED_MAX_REL_TOL * absmax,
        "logit_max_abs_diff": worst["free"], "logit_rms_diff": rms["free"],
        "ref_absmax": absmax, "ref_rms": rms["ref"],
        "routing_agree": routing_agree, "routing_sets": sets,
        "argmax_agree": argmax_agree, "positions": compared,
        "slots": S, "blocks": blocks, "denoising_steps": steps,
        "step_tokens_agree": int(step_agree.sum()),
        "step_tokens": int(step_toks.sum()),
        "step_tokens_agree_min_slot": float(step_share.min()),
    }
