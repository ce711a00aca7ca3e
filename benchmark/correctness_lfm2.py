"""The comparison that decides `correct` in the agent-session cell of a
gated-short-convolution hybrid with experts (LFM2-24B-A2B).

WHAT IS COMPARED IS WHAT THE WINDOW SERVED. After the timed window the
cell's child hands over a sample of the requests the engine finished in
it (serve_agent_holder.sample_served: the longest reply, a session's
first turn, whose prefix hit is the shared system prompt alone, and the
deepest turn whose hit passed 4,096 tokens), each as its prompt's token
ids, the number of them the engine took from its prefix cache, and the
greedy tokens it streamed. Two computations run over each sampled
stream, both teacher-forced on the SERVED tokens:

  - the plain reference's full forward (reference/lfm2_ref.py: float32,
    no cache, no chunks, every expert on every token) over the prompt
    and every served token, from position 0, HANDED the twin's experts
    at every position and expert layer (`forced`): with sixty-four
    sigmoid scores and a bias a token, the fourth and fifth lie within
    bf16 of each other at one (token, layer) pair in ten, and a
    reference that chose by itself read 7-10 % from the program as
    served on the chip (PERF.md section 6, PR 56), which hides every
    fault under it. What it would have chosen comes back too;
  - the TWIN of the served programs, run the way the engine runs them,
    in a pool of its own with one slot a sampled request and one more
    for the donors: every slot first holds ANOTHER occupant (a seeded
    prompt prefilled into it, so that a state which is neither zeroed
    nor handed over is not zero). A request with a hit of c tokens
    finds them as the engine's cache held them: a DONOR prefills the
    prompt's first c tokens into pages of its own in the
    configuration's chunks (which leaves the page-edge snapshots,
    `paged_kv.CONV_EDGE`), the request's block table takes those pages,
    `paged_kv.handover_state` copies the last one's snapshot into the
    request's slot, and `paged_prefill(return_routing=True)` runs the
    suffix from position c. Between the chunks of a later prompt the
    lanes already live take a decode step, as they do in the engine;
    then `paged_decode_chunk(chunk=1, return_routing=True)` fed the
    served token at every step, to the stream's end.

What decides (each limit between two readings on the chip, PERF.md
section 6, PR 56):

  1. `head`: over each stream's first HEAD + 1 rows (the prefill's row
     and the first decode steps), the twin's logits against the
     reference's: root mean square of the difference <= RMS_REL_TOL of
     the reference's, largest difference <= MAX_REL_TOL of the largest
     |logit|;
  2. `tail`: the same two over each stream's LAST `tail` rows;
  3. `handover`: the same two over the rows RIGHT BEHIND each hit:
     positions c and c + 1, read by two one- and two-token prefills
     from the handed-over state (the state is then handed over again
     for the suffix proper). A gated short convolution's state is two
     rows, so a wrong one moves the two tokens behind it in every conv
     layer and little else: hundreds of tokens later, in the reply's
     rows, it has drowned in bf16. This clause reads it where it
     stands. The first-turn stream's hit is the system prompt's pages,
     the deep-turn stream's a history's;
  4. `router`: `qwen2.router_logits`, the function the served step
     calls, on a seeded [256, d] input against numpy float64: largest
     error <= ROUTER_F32_TOL of the largest |logit|. The configuration
     states a float32 router; logits cannot hold the program to it (a
     bfloat16 router flips near-ties that bf16 activations flip
     anyway), so the clause reads the function itself;
  5. `routing`: `routing_agree`, the share of (token, expert layer)
     pairs at which the twin's four experts are the four the reference
     would have chosen on the same states, >= ROUTING_AGREE_MIN: 1-3
     are handed the choice and cannot see a program that chooses
     wrongly (by the scores without their bias, or a wrong expert at
     one token in five: the floor lies between that and bf16's own
     near-ties);
  6. `experts`: the expert layer ALONE (`qwen2._moe` with the first
     expert layer's weights, the configuration's dtype and kernels) on
     EXPERT_ROWS seeded rows against the reference's expert layer
     handed the same experts: rms <= EXPERT_RMS_REL_TOL. Ten layers of
     bf16 lie over the routed weights in the logits (a bias of 0.05
     left in the weights reads 2.8 % there where bf16 reads 2.2 %);
     here nothing does (5.3 % against 0.39 %);
  7. `served`: `served_ref_agree`, the share of ALL served tokens of
     the sample that are the reference's argmax at their position, >=
     SERVED_REF_MIN, and `served_twin_agree`, the share that are the
     twin's, >= SERVED_TWIN_MIN. This clause holds the ENGINE (slots,
     pages, the hand-over at every hit, 96 lanes) to the reference;
     1-3 hold the function's precision. Every run also reads the
     clause on the WRONG pairing (`served_ref_agree_swapped`).

Without `served` (tools/controls_lfm2.py and the CPU tests, where no
engine runs) the prompts are seeded ones of `prompt_tokens` with hits
of `cached_tokens`, and the streams are made here by the decode program
AS THE ENGINE DISPATCHES IT (`dispatched`, the configuration's
`decode_chunk`), `decode_chunks` chunks from the twin's first token.
"""

from __future__ import annotations

import numpy as np

# Each limit lies between two readings at the published widths on the
# chip, near their geometric middle (my chip runs, PR 56: the cell at
# nineteen seeds, the thirteen controls at seeds 2147483999 and
# 2149000001; PERF.md section 6 has every control's reading): bf16 as
# served, and the nearest control of tools/controls_lfm2.py that must
# fail by it. Where the second seed read nearer, it is named.
# rms, the reference handed the twin's experts: bf16 as served 2.05-2.34
# % (head, tail and the rows behind a hit alike; nineteen runs of the
# cell and the controls' runs); q/k norm left out 5.55 % behind the hit
# (6.9-7.2 % head and tail; 11.5-13.8 % at the second seed); a wrong state at a hit 124-134 % behind it and
# 2.2 % everywhere else.
RMS_REL_TOL = 3.5e-2
# largest difference: bf16 1.96-2.65 %; q/k norm left out 5.35 %.
MAX_REL_TOL = 3.8e-2
# Served tokens that are the reference's argmax (the reference handed
# the twin's experts): bf16 as served 0.926-0.961 over the cell's 621
# tokens, 0.910 and 0.946 in the controls; a dispatched program that is
# not the compared one 0.755 and 0.581 (fp8 0.457, another request's
# stream 0.0).
SERVED_REF_MIN = 0.83
# Served tokens that are the twin's: 0.958-0.994 in the cell (96 lanes
# served, 3 in the twin: near-ties flip in bf16), 1.0 in the controls; a
# dispatched program that is not the compared one 0.752 and 0.584.
SERVED_TWIN_MIN = 0.86
# `router_logits` against float64: float32 at full precision 1.6e-7 on
# the chip; a bfloat16 product 3.6e-3.
ROUTER_F32_TOL = 2.4e-5
# The expert layer alone: bf16 as served 0.39 %; the bias in the weights
# 5.28 % and 3.69 % at the second seed (2.7-2.9 % in the logits, where
# bf16 reads 2.2); the layer's own kernels rounded to fp8 5.82 %.
EXPERT_RMS_REL_TOL = 1.4e-2
EXPERT_ROWS = 512
# (token, expert layer) pairs at which the twin chose the four experts
# the reference would have chosen on the same states: bf16 as served
# 0.926-0.936 over nineteen runs of the cell and 0.934 in the controls
# at both seeds (near-ties between the fourth and the fifth score); a
# program that replaces one of a token's four at one token in five
# 0.761 and 0.758 (q/k norm left out 0.771 and 0.600, the selection
# without its bias 0.185 and 0.181, fp8 0.299 and 0.294). Set at 0.83
# from the prediction (0.93 x 0.8) before that control was read; the
# geometric middle of the readings is 0.84.
ROUTING_AGREE_MIN = 0.83
OCCUPANT_TOKENS = 48  # the prompt each slot's last occupant left


def router_error(cfg, seed: int) -> float:
    """`qwen2.router_logits` on seeded inputs at the configuration's
    widths against numpy float64: largest error over largest |logit|."""
    import jax.numpy as jnp

    from oryx_tpu.models import qwen2

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((256, cfg.llm.hidden_size)).astype(np.float32)
    w = (rng.standard_normal(
        (cfg.llm.hidden_size, cfg.llm.num_experts)) * 0.02).astype(np.float32)
    got = np.asarray(qwen2.router_logits(jnp.asarray(x), jnp.asarray(w)))
    want = x.astype(np.float64) @ w.astype(np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def expert_error(params, sizes: dict, program, seed: int) -> float:
    """`qwen2._moe` with the program's first expert layer on seeded
    rows, in its dtype and with its kernels, against the reference's
    expert layer (float32, `params`) handed the same experts: rms of
    the difference over the reference's rms. program: (llm params,
    OryxConfig), or a function that makes the pair now (a control's
    lower-precision copy of the layer lives for this call alone)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import oryx, qwen2

    from benchmark.reference import lfm2_ref as ref

    p_params, p_cfg = program() if callable(program) else program
    llm = p_cfg.llm
    dtype = oryx.compute_dtype(p_cfg)
    x = jax.random.normal(
        jax.random.key(seed % (2**31 - 1)), (EXPERT_ROWS, llm.hidden_size),
        jnp.float32).astype(dtype)

    @jax.jit
    def run(x, kernel, bias, experts):
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), experts)
        return qwen2._moe(
            llm, x, kernel[0], flat, jnp.asarray(0, jnp.int32),
            impl=p_cfg.attn_impl, router_bias=bias[0])

    router = p_params["layers"]["router"]
    got, routing = run(
        x, router["kernel"], router["bias"], p_params["layers"]["experts"])
    got = np.asarray(got, np.float64)
    del p_params, router  # a control's copy goes before the reference runs
    first = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)  # noqa: E731

    @jax.jit
    def want(x, router, experts, chosen):
        with jax.default_matmul_precision("highest"):
            return ref.experts(
                x.astype(jnp.float32), router, experts, sizes, chosen)[0]

    exact = want(x, first(params["layers"]["router"]),
                 first(params["layers"]["experts"]), routing["ids"])
    d = got - np.asarray(exact, np.float64)
    return float(np.sqrt(np.sum(d * d) / np.sum(
        np.square(np.asarray(exact, np.float64)))))


def logit_check(params, cfg, seed: int, *, sizes: dict, page_size: int,
                prefill_chunk: int, decode_chunk: int, max_ctx: int,
                head: int = 8, tail: int = 32,
                prompt_tokens=(2600, 700, 5000),
                cached_tokens=(2048, 0, 4480), decode_chunks: int = 4,
                prompts=None, cached=None, served=None, program=None,
                dispatched=None, handover=None, expert_program=None) -> dict:
    """params/cfg: what the reference computes with (the llm subtree
    and OryxConfig; the reference reads `sizes`, the configuration
    file's published keys, and nothing of cfg). prompts, cached, served:
    the sampled requests' prompt ids, the tokens of each that the engine
    took from its prefix cache, and the tokens it streamed (the cell);
    without them seeded prompts of `prompt_tokens` with hits of
    `cached_tokens`, and streams made here by `dispatched`, the decode
    program as the engine dispatches it (default
    `generate.paged_decode_chunk`; a control puts another here).
    program: (llm params, OryxConfig) the twin runs with, default the
    same. handover: (kv, page, slot) -> kv, default
    `paged_kv.handover_state`. expert_program: what the `experts`
    clause runs with (`expert_error`), default `program` (the controls
    differ in all three)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.models import oryx, qwen2
    from oryx_tpu.ops import paged_kv

    from benchmark.reference import lfm2_ref as ref

    llm = cfg.llm
    p_params, p_cfg = program or (params, cfg)
    hand = handover or paged_kv.handover_state
    dtype = oryx.compute_dtype(p_cfg)
    common = dict(attn_impl=p_cfg.attn_impl, compute_dtype=dtype)
    rng = np.random.default_rng(seed)
    if prompts is None:
        prompts = [rng.integers(3, llm.vocab_size, n) for n in prompt_tokens]
        cached = list(cached_tokens)
    prompts = [np.asarray(ids, np.int32) for ids in prompts]
    lens = [len(ids) for ids in prompts]
    S = len(prompts)
    # A hit ends on a page edge and leaves a token to prefill.
    cached = [min(int(c), n - 1) // page_size * page_size
              for c, n in zip(cached, lens)]
    maxp = max_ctx // page_size
    # Slot s < S a sampled request, slot S the donors'; pages: a table
    # a request, then a table a donor.
    bt = np.arange(2 * S * maxp, dtype=np.int32).reshape(2 * S, maxp)
    for s in range(S):
        bt[s, :cached[s] // page_size] = bt[S + s, :cached[s] // page_size]
    donor_bt = jnp.asarray(bt[S:])
    bt = jnp.asarray(bt[:S])
    # (the decode chunk's lanes are the S + 1 slots; the donors' rides
    # as finished behind an unused table row.)
    lanes_bt = jnp.concatenate([bt, donor_bt[:1]])
    one = (jnp.zeros((1,), jnp.float32), jnp.ones((1,), jnp.float32),
           jnp.zeros((1,), jnp.int32))
    greedy = (jnp.zeros((S + 1,), jnp.float32),
              jnp.ones((S + 1,), jnp.float32), jnp.zeros((S + 1,), jnp.int32))
    occupants = [rng.integers(3, llm.vocab_size, OCCUPANT_TOKENS)
                 for _ in range(S + 1)]

    def new_pool():
        return qwen2.init_paged_kv_cache(
            p_cfg.llm, 2 * S * maxp, page_size, dtype=dtype, num_slots=S + 1)

    def chunks_of(slot, table, ids, start=0, stop=None, seen=None):
        """The prefill dispatches of ids[start:stop] at `slot` behind
        `table` [1, maxp], in order, as closures kv -> (kv, first token,
        logits [V]); `seen(off, ids)`: handed each chunk's expert ids
        [expert layers, real rows, K]."""
        n = len(ids) if stop is None else stop
        emb = p_params["embed"]["weight"][jnp.asarray(ids[:n])][None]
        emb = generate_lib.pad_embeds_for_chunks(
            emb.astype(dtype), prefill_chunk)
        out = []
        for off in range(start, n, prefill_chunk):
            end = min(off + prefill_chunk, n)

            def run(kv, off=off, end=end):
                kv, tok, _, routing = generate_lib.paged_prefill(
                    p_params, p_cfg.llm,
                    generate_lib.slice_embeds(
                        emb, jnp.asarray(off, jnp.int32),
                        width=prefill_chunk),
                    jnp.asarray([end], jnp.int32), table, kv,
                    jnp.asarray([off], jnp.int32),
                    jax.random.split(jax.random.key(0), 1), *one,
                    slots=jnp.asarray([slot], jnp.int32),
                    return_routing=True, **common)
                if seen is not None:
                    seen(off, np.asarray(routing["ids"])[:, :end - off])
                return kv, int(np.asarray(tok)[0]), routing["logits"][0]

            out.append(run)
        return out

    def occupy(kv):
        for s, ids in enumerate(occupants):
            table = bt[s:s + 1] if s < S else donor_bt[:1]
            for run in chunks_of(s, table, ids):
                kv, _, _ = run(kv)
        return kv

    def donate(kv, seen=None):
        """The cached prefixes, as the engine's cache held them: each
        prefilled by a donor at the donors' slot into pages of its own,
        which the request's table shares."""
        for s, ids in enumerate(prompts):
            if cached[s]:
                for run in chunks_of(S, donor_bt[s:s + 1], ids, 0, cached[s],
                                     seen and seen(s)):
                    kv, _, _ = run(kv)
        return kv

    def hand_over(kv, s):
        last = int(donor_bt[s, cached[s] // page_size - 1])
        return hand(kv, jnp.asarray(last, jnp.int32), jnp.asarray(s, jnp.int32))

    lane_keys = jax.random.split(jax.random.key(1), S + 1)  # greedy: unused

    def lanes(tok, length, live):
        pad = lambda a, v: list(a) + [v]  # noqa: E731 - the donors' lane
        return (jnp.asarray(pad(tok, 0), jnp.int32),
                jnp.asarray(pad(length, 0), jnp.int32),
                ~jnp.asarray(pad(live, False), bool),
                jnp.zeros((S + 1, 0), jnp.int32), lane_keys)

    def admit(kv, s, between=None, seen=None):
        """Request s as the engine admits it: the hit's state handed
        over, the suffix prefilled chunk by chunk (`between`: what runs
        between two chunks). Returns (kv, first token, its logits)."""
        if cached[s]:
            kv = hand_over(kv, s)
        for run in chunks_of(s, bt[s:s + 1], prompts[s], cached[s],
                             seen=seen and seen(s)):
            kv, tok, logits = run(kv)
            if between is not None:
                kv = between(kv)
        return kv, tok, logits

    if served is None:
        # No engine here: the streams are the decode program's as the
        # engine dispatches it, from the prefill's first token.
        kv, first = donate(occupy(new_pool())), []
        for s in range(S):
            kv, tok, _ = admit(kv, s)
            first.append(tok)
        served = [[] for _ in range(S)]
        state = lanes(first, lens, np.ones(S, bool))
        for _ in range(decode_chunks):
            out = (dispatched or generate_lib.paged_decode_chunk)(
                p_params, p_cfg.llm, kv, lanes_bt, *state, *greedy,
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

    kv = donate(occupy(new_pool()), into)
    got = [{} for _ in range(S)]  # row k -> the twin's logits
    behind = [[] for _ in range(S)]  # the rows behind the hit
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
            p_params, p_cfg.llm, kv, lanes_bt,
            *lanes(tok, [lens[s] + done[s] for s in range(S)], on),
            *greedy, chunk=1, eos=-1, return_routing=True, **common)
        nxt, ids = np.asarray(out[1]), np.asarray(out[-1])[0]  # [Lm, S+1, K]
        for s in np.nonzero(on)[0]:
            routed[s][:, lens[s] + done[s]] = ids[:, s]
            done[s] += 1
            twin[s].append(int(nxt[s]))
            if done[s] in keep[s]:
                got[s][done[s]] = np.asarray(out[-2][s, 0], np.float32)
        return out[0], True

    keep = [set(kept(s)[0]) | set(kept(s)[1]) for s in range(S)]
    for s, ids in enumerate(prompts):
        if cached[s]:
            # The rows right behind the hit, each from the handed-over
            # state: a prefill of one token, then of two.
            for k in (1, 2):
                kv = hand_over(kv, s)
                run, = chunks_of(s, bt[s:s + 1], ids, cached[s],
                                 cached[s] + k)
                kv, _, logits = run(kv)
                behind[s].append(np.asarray(logits, np.float32))
        kv, tok, logits = admit(kv, s, lambda kv: step(kv)[0], into)
        live[s] = True
        twin[s].append(tok)
        got[s][0] = np.asarray(logits, np.float32)
    more = True
    while more:
        kv, more = step(kv)
    del kv

    parts = {n: [0.0, 0.0, 0.0, 0.0] for n in ("head", "tail", "handover")}
    ref_pick, by_stream = [], []
    same = sets = 0
    for s, ids in enumerate(prompts):
        n, stream = lens[s], np.asarray(served[s], np.int32)
        c = cached[s]
        rows_at = ([c, c + 1] if c else []) + list(
            range(n - 1, n - 1 + total[s]))
        # The reference handed the twin's experts: the same function in
        # two precisions; what it would have chosen itself comes back.
        rows, chose = ref.logits(
            params, sizes, np.concatenate([ids, stream[:-1]]), rows=rows_at,
            forced=routed[s], return_chosen=True)
        own = np.sort(np.stack([np.asarray(c) for c in chose]), axis=-1)
        same += int(np.sum(np.all(own == np.sort(routed[s], -1), axis=-1)))
        sets += own.shape[0] * own.shape[1]
        edge, rows = rows[:len(rows_at) - total[s]], rows[
            len(rows_at) - total[s]:]
        ref_pick.append(np.asarray(jnp.argmax(rows, axis=-1)))
        mine = {}
        pairs = [(name, np.asarray(rows[jnp.asarray(ks)], np.float32),
                  np.stack([got[s][k] for k in ks]))
                 for name, ks in zip(("head", "tail"), kept(s)) if ks]
        if c:
            pairs.append(("handover", np.asarray(edge, np.float32),
                          np.stack(behind[s])))
        for name, want, have in pairs:
            d2 = float(np.sum(np.square(have - want, dtype=np.float64)))
            r2 = float(np.sum(np.square(want, dtype=np.float64)))
            acc = parts[name]
            acc[0] += d2
            acc[1] += r2
            acc[2] = max(acc[2], float(np.max(np.abs(have - want))))
            acc[3] = max(acc[3], float(np.max(np.abs(want))))
            mine[name] = float(np.sqrt(d2 / max(r2, 1e-30)))
        by_stream.append(mine)
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
        # (no hit in the sample: nothing was handed over, and the cell's
        # `check_sample_kinds` says whether that is a fault.)
        "handover": within("handover") or not any(cached),
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
        "router_error": router, "router_f32_tol": ROUTER_F32_TOL,
        "expert_rms_rel": experts, "expert_rms_rel_tol": EXPERT_RMS_REL_TOL,
        "routing_agree": routing_agree,
        "routing_agree_min": ROUTING_AGREE_MIN,
        "served_ref_agree": served_ref, "served_twin_agree": served_twin,
        "served_ref_agree_swapped": swapped,
        "served_ref_min": SERVED_REF_MIN, "served_twin_min": SERVED_TWIN_MIN,
        "served_tokens": sum(total), "slots": S,
        "prompt_tokens": lens, "cached_tokens": cached,
        "stream_tokens": total,
        "rows_compared": sum(len(k) for k in keep) + 2 * sum(
            1 for c in cached if c),
    }
