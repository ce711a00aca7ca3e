"""The comparison that decides `correct` in the agent-reasoning cell of a
Mamba-2 hybrid with latent experts (NVIDIA-Nemotron-3-Super-120B-A12B).

WHAT IS COMPARED IS WHAT THE WINDOW SERVED. After the timed window the
cell's child hands over a sample of the requests the engine finished in
it (serve_reasoning_moe_holder.sample_served: the longest answer of
2,048 tokens or more, a prompt of more than one prefill chunk, a
one-chunk prompt), each as its prompt's token ids and the greedy tokens
the engine streamed. Two computations run over each sampled stream, both
teacher-forced on the SERVED tokens:

  - the plain reference's full forward (reference/nemotron_h_ref.py:
    float32, a token-by-token scan through every mixer, a loop over the
    held experts, no cache, no chunks) over the prompt and every served
    token, HANDED the twin's experts at every position and expert layer
    (`forced`; correctness_lfm2.py says why: with 512 sigmoid scores
    the 22nd and the 23rd lie within bf16 of each other at most
    tokens). What it would have chosen comes back too;
  - the TWIN of the served programs, run the way the engine runs them
    (correctness_jamba.py's, with `return_routing`): a pool of its own
    with one slot a sampled request, every slot first holding ANOTHER
    occupant; the prompts through `paged_prefill` in the
    configuration's chunks (the mixer's chunked form, chunk n + 1 from
    the state chunk n left, the last right-padded), the lanes already
    live taking a decode step between two chunks; then
    `paged_decode_chunk(chunk=1)` fed the served token at every step,
    through the slot's state (`_ssd_step`), to the stream's end.

What decides (each limit between two readings on the chip, PERF.md
section 6, PR 60):

  1. `head`: over each stream's first HEAD + 1 rows, the twin's logits
     against the reference's: rms of the difference <= RMS_REL_TOL of
     the reference's rms, largest difference <= MAX_REL_TOL of the
     largest |logit|;
  2. `tail`: the same two over each stream's LAST `tail` rows, where a
     state that drifts over two thousand steps would show;
  3. `state`: the twin's recurrent state after its last step, read by
     its own bits: the share of its non-zero float32 elements that a
     bfloat16 holds exactly <= STATE_BF16_MAX (the configuration states
     a float32 state; logits cannot hold the program to it);
  4. `router`: `qwen2.router_logits` against numpy float64
     (correctness_lfm2.router_error);
  5. `routing`: `routing_agree`, the share of (token, expert layer)
     pairs at which the twin's 22 experts are the 22 the reference
     would have chosen on the same states, >= ROUTING_AGREE_MIN (1-2
     are handed the choice and cannot see a program that chooses
     wrongly);
  6. `experts`: the expert layer ALONE (`qwen2._moe` with the first
     expert layer's weights, the configuration's dtype and kernels) on
     EXPERT_ROWS seeded rows against the reference's `expert_layer`
     handed the same experts: rms <= EXPERT_RMS_REL_TOL. Eleven layers
     of bf16 lie over the layer's parts in the logits; here nothing
     does, so the scale, `W_up` and the shared expert each show whole;
  7. `served`: `served_ref_agree`, the share of ALL served tokens of
     the sample that are the reference's argmax at their position, >=
     SERVED_REF_MIN, and `served_twin_agree` >= SERVED_TWIN_MIN: this
     clause holds the ENGINE (slots, the zeroed state, 96 lanes) to
     the reference. Every run also reads it on the WRONG pairing
     (`served_ref_agree_swapped`).

Without `served` (tools/controls_nemotron.py and the CPU tests, where no
engine runs) the prompts are seeded ones of `prompt_tokens` and the
streams are made here by the decode program AS THE ENGINE DISPATCHES IT
(`dispatched`, the configuration's `decode_chunk`), `decode_chunks`
chunks from the twin's first token.
"""

from __future__ import annotations

import numpy as np

from benchmark.correctness_lfm2 import router_error

# Each limit lies between two readings at the published widths on the
# chip, near their geometric middle (my chip runs, PR 60: the cell at
# thirteen seeds in fourteen runs, the seven controls at seed 2147483999; PERF.md section
# 6 has every control's reading): bf16 as served, and the nearest
# control of tools/controls_nemotron.py that must fail by it.
# rms, the reference handed the twin's experts: bf16 as served
# 1.25-1.29 % (head and tail alike, 2,400 steps in; fifteen runs); top
# 21 for top 22 3.64 % (tail; 4.01 head), a position term switched on
# 4.54 % (tail; 6.05 head), the scale 5 left out 13.1 %, W_up skipped
# 20.7 %, the gate after the group norm 24.4 %, the shared expert left
# out 130 %.
RMS_REL_TOL = 2.2e-2
# largest difference: bf16 1.06-1.48 %; top 21 for top 22 5.30 %, a
# position term 6.03 %.
MAX_REL_TOL = 2.8e-2
# Served tokens that are the reference's argmax (the reference handed
# the twin's experts): bf16 as served 0.961-0.975 over the cell's 4,230
# tokens (0.961 in the controls' run); the scale 5 left out 0.728, W_up
# skipped 0.608, the gate after the norm 0.501 (top 21 for top 22 reads
# 0.923 and a position term 0.896: `head` and `tail` refuse those).
SERVED_REF_MIN = 0.83
# Served tokens that are the twin's: 0.977-0.988 in the cell (96 lanes
# served, 3 in the twin: near-ties flip in bf16), 1.0 in the controls
# (their streams are the compared program's own); another request's
# stream reads 0.0. No control of this tool reads between: the floor
# is PR 56's form (a dispatched program that is not the compared one
# read 0.58-0.75 there).
SERVED_TWIN_MIN = 0.86
# `router_logits` against float64: float32 at full precision
# 4.3-6.1e-7 on the chip; a bfloat16 product 3.6e-3 (PR 56's reading of
# the same function).
ROUTER_F32_TOL = 2.4e-5
# The expert layer alone: bf16 as served 0.289-0.290 %; top 21 for top
# 22 2.12 %, the scale 5 left out 8.15 %, W_up skipped 12.9 %, the
# shared expert left out 99.5 %.
EXPERT_RMS_REL_TOL = 0.8e-2
EXPERT_ROWS = 512
# (token, expert layer) pairs at which the twin chose the 22 experts the
# reference would have chosen on the same states: bf16 as served
# 0.793-0.802 (the 22nd and the 23rd of 512 sigmoid scores lie within
# bf16 of each other at one pair in five); a position term 0.621, top 21
# for top 22 0.606 (its states drift), the scale 5 left out 0.225, the
# gate after the norm 0.036.
ROUTING_AGREE_MIN = 0.70
# Non-zero state elements a bfloat16 holds exactly: float32 as served
# 3.2-3.7e-5 (~2 ** -15); a state kept in bfloat16 1.0.
STATE_BF16_MAX = 0.01
OCCUPANT_TOKENS = 48  # the prompt each slot's last occupant left


def expert_error(params, sizes: dict, program, seed: int) -> float:
    """`qwen2._moe` with the program's first expert layer (its latent
    projections and shared expert) on seeded rows, in its dtype and
    with its kernels, against the reference's `expert_layer` (float32,
    `params`) handed the same experts: rms of the difference over the
    reference's rms. program: (llm params, OryxConfig), or a function
    that makes the pair now."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import oryx, qwen2

    from benchmark.reference import nemotron_h_ref as ref

    p_params, p_cfg = program() if callable(program) else program
    llm = p_cfg.llm
    dtype = oryx.compute_dtype(p_cfg)
    x = jax.random.normal(
        jax.random.key(seed % (2**31 - 1)), (EXPERT_ROWS, llm.hidden_size),
        jnp.float32).astype(dtype)
    first = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)  # noqa: E731

    @jax.jit
    def run(x, layers):
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), layers["experts"])
        router = first(layers["router"])
        return qwen2._moe(
            llm, x, router["kernel"], flat, jnp.asarray(0, jnp.int32),
            impl=p_cfg.attn_impl, router_bias=router.get("bias"),
            shared=first(layers["shared"]), latent=first(layers["latent"]))

    got, routing = run(
        x, {k: p_params["layers"][k] for k in ref.FFN_STACKS})
    got = np.asarray(got, np.float64)
    del p_params  # a control's copy goes before the reference runs
    sz = tuple(sorted(sizes.items()))

    @jax.jit
    def want(x, ffn, chosen):
        with jax.default_matmul_precision("highest"):
            return ref.expert_layer(
                x.astype(jnp.float32), ffn, dict(sz), chosen)[0]

    exact = want(x, {k: first(params["layers"][k]) for k in ref.FFN_STACKS},
                 routing["ids"])
    d = got - np.asarray(exact, np.float64)
    return float(np.sqrt(np.sum(d * d) / np.sum(
        np.square(np.asarray(exact, np.float64)))))


def logit_check(params, cfg, seed: int, *, sizes: dict, page_size: int,
                prefill_chunk: int, decode_chunk: int, max_ctx: int,
                head: int = 16, tail: int = 64,
                prompt_tokens=(300, 1500, 40), decode_chunks: int = 4,
                prompts=None, served=None, program=None,
                dispatched=None, expert_program=None) -> dict:
    """params/cfg: what the reference computes with (the llm subtree
    and OryxConfig; the reference reads `sizes`, the configuration
    file's published keys, and nothing of cfg). prompts, served: the
    sampled requests' prompt ids and the tokens the engine streamed for
    each (the cell); without them seeded prompts of `prompt_tokens`,
    and streams made here by `dispatched`, the decode program as the
    engine dispatches it (default `generate.paged_decode_chunk`; a
    control puts another here). program: (llm params, OryxConfig) the
    twin runs with, default the same (the controls differ here);
    expert_program: the same for the `experts` clause alone."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.models import oryx, qwen2
    from oryx_tpu.ops import paged_kv

    from benchmark.reference import nemotron_h_ref as ref

    llm = cfg.llm
    p_params, p_cfg = program or (params, cfg)
    dtype = oryx.compute_dtype(p_cfg)
    common = dict(attn_impl=p_cfg.attn_impl, compute_dtype=dtype)
    rng = np.random.default_rng(seed)
    if prompts is None:
        prompts = [rng.integers(3, llm.vocab_size, n) for n in prompt_tokens]
    prompts = [np.asarray(ids, np.int32) for ids in prompts]
    lens = [len(ids) for ids in prompts]
    S = len(prompts)
    maxp = max_ctx // page_size
    bt = jnp.arange(S * maxp, dtype=jnp.int32).reshape(S, maxp)
    one = (jnp.zeros((1,), jnp.float32), jnp.ones((1,), jnp.float32),
           jnp.zeros((1,), jnp.int32))
    greedy = (jnp.zeros((S,), jnp.float32), jnp.ones((S,), jnp.float32),
              jnp.zeros((S,), jnp.int32))
    occupants = [rng.integers(3, llm.vocab_size, OCCUPANT_TOKENS)
                 for _ in range(S)]

    def new_pool():
        return qwen2.init_paged_kv_cache(
            p_cfg.llm, S * maxp, page_size, dtype=dtype, num_slots=S)

    def chunks_of(s, ids, seen=None):
        """Lane s's prefill dispatches, in order, as closures kv -> (kv,
        first token, logits [V]); `seen(off, ids)`: handed each chunk's
        expert ids [expert layers, real rows, K]."""
        n = len(ids)
        emb = p_params["embed"]["weight"][jnp.asarray(ids)][None]
        emb = generate_lib.pad_embeds_for_chunks(
            emb.astype(dtype), prefill_chunk)
        out = []
        for off in range(0, n, prefill_chunk):
            end = min(off + prefill_chunk, n)

            def run(kv, off=off, end=end):
                kv, tok, _, routing = generate_lib.paged_prefill(
                    p_params, p_cfg.llm,
                    generate_lib.slice_embeds(
                        emb, jnp.asarray(off, jnp.int32),
                        width=prefill_chunk),
                    jnp.asarray([end], jnp.int32), bt[s:s + 1], kv,
                    jnp.asarray([off], jnp.int32),
                    jax.random.split(jax.random.key(0), 1), *one,
                    slots=jnp.asarray([s], jnp.int32), return_routing=True,
                    **common)
                if seen is not None:
                    seen(off, np.asarray(routing["ids"])[:, :end - off])
                return kv, int(np.asarray(tok)[0]), routing["logits"][0]

            out.append(run)
        return out

    def occupy(kv):
        for s, ids in enumerate(occupants):
            for run in chunks_of(s, ids):
                kv, _, _ = run(kv)
        return kv

    lane_keys = jax.random.split(jax.random.key(1), S)  # greedy: unused

    def lanes(tok, length, live):
        return (jnp.asarray(tok, jnp.int32), jnp.asarray(length, jnp.int32),
                ~jnp.asarray(live, bool), jnp.zeros((S, 0), jnp.int32),
                lane_keys)

    if served is None:
        # No engine here: the streams are the decode program's as the
        # engine dispatches it, from the prefill's first token.
        kv, first = occupy(new_pool()), []
        for s, ids in enumerate(prompts):
            for run in chunks_of(s, ids):
                kv, tok, _ = run(kv)
            first.append(tok)
        served = [[] for _ in range(S)]
        state = lanes(first, lens, np.ones(S, bool))
        for _ in range(decode_chunks):
            out = (dispatched or generate_lib.paged_decode_chunk)(
                p_params, p_cfg.llm, kv, bt, *state, *greedy,
                chunk=decode_chunk, eos=-1, **common)
            kv, state = out[0], out[1:6]
            for s in range(S):  # a chunk emits the tokens it was fed
                served[s] += list(np.asarray(out[6])[s])
        for s in range(S):
            served[s].append(np.asarray(state[0])[s])
        del kv
    served = [[int(t) for t in toks] for toks in served]
    total = [len(t) for t in served]
    assert max(n + t for n, t in zip(lens, total)) + 1 <= max_ctx

    def kept(s):
        """Rows of stream s that are compared: (head rows, tail rows)."""
        h = list(range(min(head + 1, total[s])))
        t = [k for k in range(max(0, total[s] - tail), total[s])
             if k not in h]
        return h, t

    # The twin, the way the engine runs it. `routed[s]`: the experts it
    # chose at every position of stream s, [expert layers, tokens, K].
    Lm, K = p_cfg.llm.moe_layers, p_cfg.llm.num_experts_per_tok
    routed = [np.zeros((Lm, lens[s] + total[s] - 1, K), np.int32)
              for s in range(S)]

    def into(s):
        def seen(off, ids):
            routed[s][:, off:off + ids.shape[1]] = ids
        return seen

    kv = occupy(new_pool())
    got = [{} for _ in range(S)]  # row k -> the twin's logits
    twin = [[] for _ in range(S)]  # the twin's own greedy tokens
    done = [0] * S  # decode steps lane s has taken
    live = np.zeros(S, bool)

    def step(kv):
        """One decode step of every live lane with a token left to
        feed; lanes that are empty, prefilling or done ride as
        finished."""
        on = live & np.asarray([done[s] < total[s] - 1 for s in range(S)])
        if not on.any():
            return kv, False
        tok = [served[s][min(done[s], total[s] - 1)] for s in range(S)]
        out = generate_lib.paged_decode_chunk(
            p_params, p_cfg.llm, kv, bt,
            *lanes(tok, [lens[s] + done[s] for s in range(S)], on),
            *greedy, chunk=1, eos=-1, return_routing=True, **common)
        nxt, ids = np.asarray(out[1]), np.asarray(out[-1])[0]  # [Lm, S, K]
        for s in np.nonzero(on)[0]:
            routed[s][:, lens[s] + done[s]] = ids[:, s]
            done[s] += 1
            twin[s].append(int(nxt[s]))
            if done[s] in keep[s]:
                got[s][done[s]] = np.asarray(out[-2][s, 0], np.float32)
        return out[0], True

    keep = [set(kept(s)[0]) | set(kept(s)[1]) for s in range(S)]
    for s, ids in enumerate(prompts):
        for run in chunks_of(s, ids, into(s)):
            kv, tok, logits = run(kv)
            kv, _ = step(kv)  # the residents decode between two chunks
        live[s] = True
        twin[s].append(tok)
        got[s][0] = np.asarray(logits, np.float32)
    more = True
    while more:
        kv, more = step(kv)
    bits = np.asarray(kv[paged_kv.SLOT_PLANES[1]]).view(np.uint32)
    bits = bits[(bits & 0x7FFFFFFF) != 0]
    state_bf16 = float(np.mean((bits & 0xFFFF) == 0)) if bits.size else 1.0
    del kv, bits

    parts = {"head": [0.0, 0.0, 0.0, 0.0], "tail": [0.0, 0.0, 0.0, 0.0]}
    ref_pick, by_stream = [], []
    same = sets = 0
    for s, ids in enumerate(prompts):
        n, stream = lens[s], np.asarray(served[s], np.int32)
        # The reference handed the twin's experts: the same function in
        # two precisions; what it would have chosen itself comes back.
        rows, chose = ref.logits(
            params, sizes, np.concatenate([ids, stream[:-1]]),
            rows=list(range(n - 1, n - 1 + total[s])),
            forced=routed[s], return_chosen=True)
        own = np.sort(np.stack([np.asarray(c) for c in chose]), axis=-1)
        mine = routed[s]
        if mine.shape[-1] == own.shape[-1]:
            same += int(np.sum(np.all(own == np.sort(mine, -1), axis=-1)))
        sets += own.shape[0] * own.shape[1]
        ref_pick.append(np.asarray(jnp.argmax(rows, axis=-1)))
        per = {}
        for name, ks in zip(("head", "tail"), kept(s)):
            if not ks:
                continue
            want = np.asarray(rows[jnp.asarray(ks)], np.float32)
            have = np.stack([got[s][k] for k in ks])
            d2 = float(np.sum(np.square(have - want, dtype=np.float64)))
            r2 = float(np.sum(np.square(want, dtype=np.float64)))
            acc = parts[name]
            acc[0] += d2
            acc[1] += r2
            acc[2] = max(acc[2], float(np.max(np.abs(have - want))))
            acc[3] = max(acc[3], float(np.max(np.abs(want))))
            per[name] = float(np.sqrt(d2 / max(r2, 1e-30)))
        by_stream.append(per)
        del rows

    def share(pairs):
        hit = count = 0
        for a, b in pairs:
            m = min(len(a), len(b))
            hit += int(np.sum(np.asarray(a[:m]) == np.asarray(b[:m])))
            count += m
        return hit / max(1, count)

    read = {}
    for name, (d2, r2, worst, absmax) in parts.items():
        read[name + "_rms_rel"] = float(np.sqrt(d2 / max(r2, 1e-30)))
        read[name + "_max_rel"] = worst / max(absmax, 1e-30)
    served_ref = share(zip(served, ref_pick))
    served_twin = share(zip(served, twin))
    swapped = share(zip(served, ref_pick[1:] + ref_pick[:1])) if S > 1 else None
    router = router_error(p_cfg, seed)
    routing_agree = same / max(1, sets)
    experts = expert_error(
        params, sizes, expert_program or (p_params, p_cfg), seed)

    def within(name):
        return bool(np.isfinite(read[name + "_rms_rel"])
                    and read[name + "_rms_rel"] <= RMS_REL_TOL
                    and read[name + "_max_rel"] <= MAX_REL_TOL)

    passed = {
        "head": within("head"), "tail": within("tail"),
        "state": state_bf16 <= STATE_BF16_MAX,
        "router": router <= ROUTER_F32_TOL,
        "routing": routing_agree >= ROUTING_AGREE_MIN,
        "experts": bool(np.isfinite(experts)
                        and experts <= EXPERT_RMS_REL_TOL),
        "served": bool(served_ref >= SERVED_REF_MIN
                       and served_twin >= SERVED_TWIN_MIN),
    }
    return {
        "ok": all(passed.values()), "passed": passed, **read,
        "rms_rel_tol": RMS_REL_TOL, "max_rel_tol": MAX_REL_TOL,
        "rms_rel_by_stream": by_stream,
        "state_bf16_share": state_bf16, "state_bf16_max": STATE_BF16_MAX,
        "router_error": router, "router_f32_tol": ROUTER_F32_TOL,
        "expert_rms_rel": experts, "expert_rms_rel_tol": EXPERT_RMS_REL_TOL,
        "routing_agree": routing_agree,
        "routing_agree_min": ROUTING_AGREE_MIN,
        "served_ref_agree": served_ref, "served_twin_agree": served_twin,
        "served_ref_agree_swapped": swapped,
        "served_ref_min": SERVED_REF_MIN, "served_twin_min": SERVED_TWIN_MIN,
        "served_tokens": sum(total), "slots": S,
        "prompt_tokens": lens, "stream_tokens": total,
        "rows_compared": sum(len(k) for k in keep),
    }
