"""The comparison that decides `correct` in the single-latent-block
cell (Mistral-Small-4).

WHAT IS COMPARED IS WHAT THE WINDOW SERVED. After the timed window the
cell's child hands over a sample of the requests the engine finished in
it (serve_docqa_child.sample_served: a cold document over 16,384
positions, cached follow-ups over and under it, a cold document under
it), each as its prompt's token ids and the greedy tokens the engine
streamed: tokens that came out of the window's own compiled programs,
with sixteen slots live, through `_advance_prefill`'s cut of the block
table, the suffix-only embeds and the prefix cache's splice. Three
computations then run over each sampled stream, every one teacher-forced
on the SERVED tokens, so that all rows share their inputs:

  - the plain reference's full forward (reference/mistral4_ref.py:
    float32, no cache, the NON-absorbed attention in query blocks), FREE
    (it routes by itself) over the prompt and every served token, and
    FORCED to the twin's expert ids (never its weights or hidden states)
    over the prompt and the first `decode_chunks * decode_chunk` of them;
  - the TWIN of the served programs: the sampled prompts go through
    `paged_prefill(return_routing=True)` in the configuration's chunks as
    live slots of one paged latent pool, each chunk handed the table
    width the scheduler would hand it (`scheduler.prefill_table_buckets`),
    then through `paged_decode_chunk(chunk=1, return_routing=True)` fed
    the served token at every step (the absorbed product over the pages
    in place). The twin is the same forward with logits and expert ids
    as further outputs: it is what lets the reference be forced, so that
    the same function is compared in two precisions.

What decides:

  1. `forced_logit_rms_diff` <= FORCED_RMS_REL_TOL of the reference's
     root mean square, and the largest single difference <=
     FORCED_MAX_REL_TOL of the largest |logit| (twin against the forced
     reference);
  2. `routing_agree`, the share of (row, layer) top-4 SETS on which
     the free run and the twin agree, >= ROUTING_AGREE_MIN: the forced
     run cannot see a program that routes wrongly;
  3. `expert_rms_rel` <= EXPERT_RMS_REL_TOL: the program's expert layer
     ALONE (`qwen2._moe` with layer 0's weights and shared expert, the
     configuration's dtype and kernels) on EXPERT_ROWS seeded rows whose
     selection is biased to the held experts, so that every one of a
     row's 4 pairs enters the grouped products, against the reference's
     expert layer forced to the same ids. One pick in four lands on a
     held expert here, so the logits see the grouped products' precision
     only faintly;
  4. the SERVED tokens: `served_ref_agree`, the share of ALL served
     tokens of the sample (some hundreds) that are the free reference's
     argmax at their position, >= SERVED_REF_MIN; and
     `served_twin_agree`, the share of each stream's first tokens that
     are the twin's, >= SERVED_TWIN_MIN. This clause is what holds the
     engine (table cut, embed offsets, splice, sixteen lanes) to the
     reference; 1-3 hold the function's precision. Every run also reads
     the clause on the WRONG pairing (stream i against stream i + 1's
     logits, `served_ref_agree_swapped`), which is what an engine that
     reads another request's pages or embeds would serve.

Without `served` (tools/controls_mistral4.py and the CPU tests, where no
engine runs) the prompts are seeded ones of `prompt_tokens` and the
streams are made here by the decode program AS THE ENGINE DISPATCHES IT
(`dispatched`: no `return_routing`, the configuration's `decode_chunk`)
from the twin's first token, on a copy of the pool.

Each limit lies between two readings at the published widths on the
chip (PERF.md section 6, PR 33): bf16 as served, and the nearest control
of tools/controls_mistral4.py that must fail by it. The free run's logit
differences are reported and decide nothing.
"""

from __future__ import annotations

import numpy as np

# Each limit is the geometric middle of two readings at the published
# widths on the chip (my chip runs, PR 33, seeds 2147483777 and
# 2147483999; PERF.md section 6 has every control's reading): bf16 as
# served, and the nearest control that must fail by it.
# (On the cell's sampled requests, all over 8k tokens, bf16 reads rms
# 1.81-1.85 % and max 1.76-2.14 %; the third session's controls, seed
# 2147484217, read within a twentieth of the first's.)
FORCED_RMS_REL_TOL = 4.5e-2  # bf16 2.06-2.08 %; the latent in fp8 9.98 %
FORCED_MAX_REL_TOL = 6e-2  # bf16 2.34-2.35 %; the latent in fp8 16.5 %
ROUTING_AGREE_MIN = 0.82  # bf16 0.925-0.927; fp8 0.717, no query scale 0.720
EXPERT_RMS_REL_TOL = 6.5e-3  # bf16 0.327-0.328 %; int8 activations 1.29 %
EXPERT_ROWS = 512
# Served tokens that are the free float32 reference's argmax (my chip
# runs, PR 33 third session): bf16 as served 0.908-0.946 over the 588
# tokens of the cell's sample (nine runs) and 0.926 over the controls'
# 68; int8 activations 0.882 (clause 3 is what fails it), the latent in
# fp8 0.721, the query's scale left out 0.691, YaRN left out 0.118,
# another slot's pages 0.044, another request's stream 0.0 (every run).
# The geometric middle of 0.908 and 0.721.
SERVED_REF_MIN = 0.8
# Served tokens that are the twin's: 0.838-1.0 (a cached follow-up reads
# latents that decode steps wrote, the twin's prefill writes them anew:
# near-ties flip); a dispatched program that is not the compared one
# 0.059.
SERVED_TWIN_MIN = 0.5
# The reference's forwards are filled up to a multiple of this: a
# stream's free forward (every served token) and its forced one (the
# twin's steps) then share a compiled layer, 10-30 s each at 18k
# positions in float32.
REF_PAD = 2048


def expert_layer_check(params, cfg, seed: int, *, program=None) -> float:
    """Clause 3: the relative rms difference of the expert layer alone
    (routed and shared) with every pair live."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import oryx, qwen2

    from benchmark.reference import mistral4_ref as ref

    llm = cfg.llm
    p_params, p_cfg = program or (params, cfg)
    dtype = oryx.compute_dtype(p_cfg)
    first, count = llm.held
    bias = jnp.zeros((llm.num_experts,), jnp.float32).at[
        first + jnp.arange(count)].set(1.0)
    x = jax.random.normal(
        jax.random.key(seed % (2**31 - 1)), (EXPERT_ROWS, llm.hidden_size),
        jnp.float32).astype(dtype)

    # The stacked weights go in whole and are viewed inside the program:
    # a sliced copy of the experts made out here would be gigabytes.
    @jax.jit
    def run(x, kernel, experts, shared, bias):
        flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), experts)
        return qwen2._moe(
            p_cfg.llm, x, kernel[0], flat, jnp.asarray(0, jnp.int32),
            impl=p_cfg.attn_impl, router_bias=bias,
            shared=jax.tree.map(lambda a: a[0], shared))

    layers = p_params["layers"]
    got, routing = run(x, layers["router"]["kernel"], layers["experts"],
                       layers["shared"], bias)
    stack = {k: params["layers"][k] for k in ("router", "experts", "shared")}
    with jax.default_matmul_precision("highest"):
        want, _ = ref._experts(x.astype(jnp.float32), stack, 0, llm,
                               routing["ids"])
    diff = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt(np.mean(diff ** 2))
                 / max(np.sqrt(np.mean(np.asarray(want, np.float64) ** 2)),
                       1e-30))


def logit_check(params, cfg, seed: int, *, page_size: int,
                prefill_chunk: int, decode_chunk: int, max_ctx: int,
                prompt_tokens=(20000, 9000, 700, 40), decode_chunks: int = 2,
                prompts=None, served=None, program=None,
                dispatched=None) -> dict:
    """params/cfg: what the reference computes with (the llm subtree
    and OryxConfig). prompts, served: the sampled requests' prompt ids
    and the tokens the engine streamed for each (the cell); without
    them seeded prompts of `prompt_tokens`, and streams made here by
    `dispatched`, the decode program as the engine dispatches it
    (default `generate.paged_decode_chunk`; a control puts another
    here). program: (llm params, OryxConfig) the twin runs with,
    default the same (the controls differ here). max_ctx: the
    engine's, which fixes the slots' table width and the widths a
    prefill chunk's table is cut to."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.models import oryx, qwen2
    from oryx_tpu.serve import scheduler

    from benchmark.reference import mistral4_ref as ref

    llm = cfg.llm
    p_params, p_cfg = program or (params, cfg)
    prefill, decode = (generate_lib.paged_prefill,
                       generate_lib.paged_decode_chunk)
    dtype = oryx.compute_dtype(p_cfg)
    common = dict(attn_impl=p_cfg.attn_impl, compute_dtype=dtype)
    if prompts is None:
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(3, llm.vocab_size, n) for n in prompt_tokens]
    prompts = [np.asarray(ids, np.int32) for ids in prompts]
    lens = [len(ids) for ids in prompts]
    S, steps = len(prompts), decode_chunks * decode_chunk
    maxp = max_ctx // page_size
    widths = scheduler.prefill_table_buckets(maxp, page_size)
    assert max(lens) + steps + 1 <= max_ctx
    kv = qwen2.init_paged_kv_cache(p_cfg.llm, S * maxp, page_size,
                                   dtype=dtype)
    bt = jnp.arange(S * maxp, dtype=jnp.int32).reshape(S, maxp)
    one = (jnp.zeros((1,), jnp.float32), jnp.ones((1,), jnp.float32),
           jnp.zeros((1,), jnp.int32))
    greedy = (jnp.zeros((S,), jnp.float32), jnp.ones((S,), jnp.float32),
              jnp.zeros((S,), jnp.int32))

    routed = [[] for _ in range(S)]  # the twin's expert ids [L, rows, K]
    got = [[] for _ in range(S)]  # the twin's logits, row by row
    twin = [[] for _ in range(S)]  # the twin's own greedy tokens
    used = set()
    for s, ids in enumerate(prompts):
        n = len(ids)
        emb = p_params["embed"]["weight"][jnp.asarray(ids)][None]
        emb = generate_lib.pad_embeds_for_chunks(emb.astype(dtype),
                                                 prefill_chunk)
        keys1 = jax.random.split(jax.random.key(0), 1)
        for off in range(0, n, prefill_chunk):
            end = min(off + prefill_chunk, n)
            reach = -(-(off + prefill_chunk) // page_size)
            table = next((w for w in widths if w >= reach), maxp)
            used.add(table * page_size)
            kv, tok, keys1, routing = prefill(
                p_params, p_cfg.llm,
                generate_lib.slice_embeds(emb, jnp.asarray(off, jnp.int32),
                                          width=prefill_chunk),
                jnp.asarray([end], jnp.int32), bt[s:s + 1, :table], kv,
                jnp.asarray([off], jnp.int32), keys1, *one,
                return_routing=True, **common,
            )
            routed[s].append(np.asarray(routing["ids"])[:, : end - off])
        got[s].append(np.asarray(routing["logits"], np.float32)[0])
        twin[s].append(int(np.asarray(tok)[0]))

    def state_of(tok, length):
        return (jnp.asarray(tok, jnp.int32), jnp.asarray(length, jnp.int32),
                jnp.zeros((S,), bool), jnp.zeros((S, 0), jnp.int32),
                jax.random.split(jax.random.key(1), S))

    if served is None:
        # No engine here: the streams are the decode program's as the
        # engine dispatches it, from the twin's first token, on a copy
        # of the pool.
        served = [[] for _ in range(S)]
        state, kv_d = state_of([t[0] for t in twin], lens), jax.tree.map(
            jnp.copy, kv)
        for _ in range(decode_chunks):
            out = (dispatched or decode)(
                p_params, p_cfg.llm, kv_d, bt, *state, *greedy,
                chunk=decode_chunk, eos=-1, **common)
            kv_d, state = out[0], out[1:6]
            for s in range(S):  # a chunk emits the tokens it was fed
                served[s] += list(np.asarray(out[6])[s])
        for s in range(S):
            served[s].append(np.asarray(state[0])[s])
        del kv_d
    served = [[int(t) for t in toks] for toks in served]
    assert min(len(t) for t in served) > steps, "a stream shorter than the twin"

    # The twin's decode, one step a dispatch, fed the SERVED token.
    for k in range(steps):
        out = decode(
            p_params, p_cfg.llm, kv, bt,
            *state_of([t[k] for t in served], [n + k for n in lens]),
            *greedy, chunk=1, eos=-1, return_routing=True, **common,
        )
        kv = out[0]
        nxt, logits, ids = np.asarray(out[1]), out[-2], out[-1]
        logits = np.asarray(logits, np.float32)  # [S, 1, V]
        ids = np.asarray(ids)  # [1, L, S, K]
        for s in range(S):
            twin[s].append(int(nxt[s]))  # what the twin would feed next
            got[s].append(logits[s, 0])
            routed[s].append(np.moveaxis(ids[:, :, s], 0, 1))
    del kv

    worst = {"free": 0.0, "forced": 0.0}
    sq = {"forced": 0.0, "free": 0.0, "ref": 0.0}
    # The first token's row (the prefill program's expanded attention)
    # and the decode rows (the absorbed walk), forced, each with the
    # reference's own sum of squares: which path a difference is in.
    phase = {"prefill": [0.0, 0.0], "decode": [0.0, 0.0]}
    by_prompt = {}
    absmax, agree, sets, compared = 0.0, 0, 0, 0
    ref_pick = []  # the free reference's argmax at every served token
    for s, ids in enumerate(prompts):
        n, stream = len(ids), np.asarray(served[s], np.int32)
        prog = np.concatenate(routed[s], axis=1)  # [L, n + steps, K]
        lg = np.stack(got[s])  # [steps + 1, V]
        # Free, over the prompt and every served token: row n - 1 + k
        # is what token k was sampled from.
        free, chosen = ref.logits(
            params, llm, np.concatenate([ids, stream[:-1]]),
            rows=list(range(n - 1, n - 1 + len(stream))), return_experts=True,
            pad_to=REF_PAD)
        free = np.asarray(free)
        ref_pick.append(free.argmax(-1))
        same = np.all(
            np.sort(prog, -1) == np.sort(
                np.asarray(chosen)[:, : n + steps], -1), axis=-1)
        agree += int(same.sum())
        sets += same.size
        want = free[: steps + 1]
        worst["free"] = max(worst["free"], float(np.max(np.abs(lg - want))))
        sq["free"] += float(np.sum(np.square(lg - want, dtype=np.float64)))
        del free, chosen
        want = np.asarray(ref.logits(
            params, llm, np.concatenate([ids, stream[:steps]]),
            rows=list(range(n - 1, n + steps)), forced_experts=prog,
            pad_to=REF_PAD))
        absmax = max(absmax, float(np.max(np.abs(want))))
        sq["ref"] += float(np.sum(np.square(want, dtype=np.float64)))
        compared += steps + 1
        worst["forced"] = max(worst["forced"],
                              float(np.max(np.abs(lg - want))))
        d2 = float(np.sum(np.square(lg - want, dtype=np.float64)))
        r2 = float(np.sum(np.square(want, dtype=np.float64)))
        sq["forced"] += d2
        by_prompt[str(n)] = float(np.sqrt(d2 / max(r2, 1e-30)))
        for name, part in (("prefill", slice(0, 1)), ("decode", slice(1, None))):
            phase[name][0] += float(np.sum(np.square(
                lg[part] - want[part], dtype=np.float64)))
            phase[name][1] += float(np.sum(np.square(
                want[part], dtype=np.float64)))
    rms = {k: float(np.sqrt(v / max(1, compared * llm.vocab_size)))
           for k, v in sq.items()}
    routing_agree = agree / max(1, sets)
    expert_rms_rel = expert_layer_check(params, cfg, seed, program=program)

    def share(pairs):
        hit = total = 0
        for a, b in pairs:
            m = min(len(a), len(b))
            hit += int(np.sum(np.asarray(a[:m]) == np.asarray(b[:m])))
            total += m
        return hit / max(1, total)

    served_ref = share(zip(served, ref_pick))
    served_twin = share(zip(served, twin))
    # Stream i against what the reference gives at stream i + 1's rows.
    swapped = share(zip(served, ref_pick[1:] + ref_pick[:1])) if S > 1 else None
    passed = {
        "forced": bool(np.isfinite(rms["forced"])
                       and rms["forced"] <= FORCED_RMS_REL_TOL * rms["ref"]
                       and worst["forced"] <= FORCED_MAX_REL_TOL * absmax),
        "routing": routing_agree >= ROUTING_AGREE_MIN,
        "experts": bool(np.isfinite(expert_rms_rel)
                        and expert_rms_rel <= EXPERT_RMS_REL_TOL),
        "served": bool(served_ref >= SERVED_REF_MIN
                       and served_twin >= SERVED_TWIN_MIN),
    }
    return {
        "ok": all(passed.values()), "passed": passed,
        "forced_logit_rms_diff": rms["forced"],
        "forced_tol": FORCED_RMS_REL_TOL * rms["ref"],
        "forced_rms_rel": rms["forced"] / max(rms["ref"], 1e-30),
        "forced_logit_max_abs_diff": worst["forced"],
        "forced_max_tol": FORCED_MAX_REL_TOL * absmax,
        "forced_max_rel": worst["forced"] / max(absmax, 1e-30),
        "forced_rms_rel_by_phase": {
            k: float(np.sqrt(d / max(r, 1e-30))) for k, (d, r) in phase.items()
        },
        "forced_rms_rel_by_prompt": by_prompt,
        "logit_max_abs_diff": worst["free"], "logit_rms_diff": rms["free"],
        "ref_absmax": absmax, "ref_rms": rms["ref"],
        "routing_agree": routing_agree, "routing_sets": sets,
        "expert_rms_rel": expert_rms_rel,
        "served_ref_agree": served_ref, "served_twin_agree": served_twin,
        "served_ref_agree_swapped": swapped,
        "served_tokens": sum(len(t) for t in served),
        "positions": compared, "slots": S, "decode_steps": steps,
        "prompt_tokens": lens, "table_positions": sorted(used),
    }
