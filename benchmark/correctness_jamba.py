"""The comparison that decides `correct` in the long-reasoning cell of a
state-space hybrid (AI21-Jamba2-3B).

WHAT IS COMPARED IS WHAT THE WINDOW SERVED. After the timed window the
cell's child hands over a sample of the requests the engine finished in
it (serve_reasoning_child.sample_served: the longest answer of 1,536
tokens or more, a two-chunk prompt, a one-chunk prompt), each as its prompt's
token ids and the greedy tokens the engine streamed. Two computations
run over each sampled stream, both teacher-forced on the SERVED tokens:

  - the plain reference's full forward (reference/jamba_ref.py:
    float32, a token-by-token scan, no cache, no chunks) over the
    prompt and every served token;
  - the TWIN of the served programs, run the way the engine runs them,
    in a pool of its own with one slot a sampled request: every slot
    first holds ANOTHER occupant (a seeded prompt prefilled into it, so
    that a state which is not zeroed is not zero); then the sampled
    prompts go through `paged_prefill(return_logits=True)` in the
    configuration's chunks at their slots (chunk n + 1 from the state
    chunk n left, the last chunk right-padded), and BETWEEN the chunks
    of a later prompt the lanes already live take a decode step, as
    they do in the engine, with the prefilling lane riding as
    `finished`; then `paged_decode_chunk(chunk=1, return_logits=True)`
    fed the served token at every step, through the slot's state, to
    the stream's end.

What decides (each limit between two readings on the chip, PERF.md
section 6, PR 40):

  1. `head`: over each stream's first HEAD + 1 rows (the prefill's row
     and the first decode steps), the twin's logits against the
     reference's: root mean square of the difference <= RMS_REL_TOL of
     the reference's, largest difference <= MAX_REL_TOL of the largest
     |logit|;
  2. `tail`: the same two over each stream's LAST `tail` rows, where a
     state that drifts over two thousand steps, or was advanced while
     its lane waited, would show;
  3. `state`: the twin's recurrent state after its last step, read by
     its own bits: the share of its non-zero float32 elements that a
     bfloat16 holds exactly (low 16 mantissa bits zero) <=
     STATE_BF16_MAX. The configuration states a float32 state; logits
     cannot hold the program to it (a state rounded to bfloat16 every
     step reads 3.61 % where float32 reads 3.59 %, 500 steps in: it
     hides inside the bf16 of everything else), so the clause reads
     the state itself;
  4. `served`: `served_ref_agree`, the share of ALL served tokens of
     the sample that are the reference's argmax at their position, >=
     SERVED_REF_MIN, and `served_twin_agree`, the share that are the
     twin's, >= SERVED_TWIN_MIN. This clause holds the ENGINE (slot
     indices, the zeroed state, 64 lanes) to the reference; 1-2 hold
     the function's precision. Every run also reads the clause on the
     WRONG pairing (stream i against the reference's picks at stream
     i + 1's rows, `served_ref_agree_swapped`), which is what an engine
     that reads another slot's state or pages would serve.

Without `served` (tools/controls_jamba.py and the CPU tests, where no
engine runs) the prompts are seeded ones of `prompt_tokens` and the
streams are made here by the decode program AS THE ENGINE DISPATCHES
IT (`dispatched`: no `return_logits`, the configuration's
`decode_chunk`), `decode_chunks` chunks from the twin's first token in
a pool of its own.
"""

from __future__ import annotations

import numpy as np

# Each limit is the geometric middle of two readings at the published
# widths on the chip (my chip runs, PR 40, seeds 2147483777 (the cell),
# 2147483999 and 2147484101 (the controls); PERF.md section 6 has every
# control's reading): bf16 as served, and the nearest control of
# tools/controls_jamba.py that must fail by it.
# rms: bf16 3.50-3.63 % (head and tail alike, 97 to 2,222 steps in,
# 27 runs);
# rotary positions switched on 8.1 % (a reused slot not zeroed 8.6 %).
RMS_REL_TOL = 5.4e-2
# largest difference: bf16 3.48-4.18 %; rotary positions 10.1 %.
MAX_REL_TOL = 6.5e-2
# Served tokens that are the float32 reference's argmax: bf16 as served
# 0.898-0.919 over the 3,235 tokens of the cell's sample (25 runs),
# 0.906-0.924 over
# the controls' 291-1,539; the conv window one token late 0.777, a
# padded chunk that moves the state 0.687, dt's norm left out 0.632,
# fp8 weights 0.306, another request's stream 0.0 (every run).
SERVED_REF_MIN = 0.83
# Served tokens that are the twin's: 0.916 in the cell (64 lanes served,
# 3 in the twin: near-ties flip in bf16), 1.0 in the controls; a
# dispatched program that is not the compared one reads low.
SERVED_TWIN_MIN = 0.75
# Non-zero state elements a bfloat16 holds exactly: float32 as served
# ~2 ** -16; a state kept in bfloat16 1.0.
STATE_BF16_MAX = 0.01
OCCUPANT_TOKENS = 48  # the prompt each slot's last occupant left


def logit_check(params, cfg, seed: int, *, sizes: dict, page_size: int,
                prefill_chunk: int, decode_chunk: int, max_ctx: int,
                head: int = 16, tail: int = 64,
                prompt_tokens=(300, 700, 40), decode_chunks: int = 4,
                prompts=None, served=None, program=None,
                dispatched=None) -> dict:
    """params/cfg: what the reference computes with (the llm subtree
    and OryxConfig; the reference reads `sizes`, the configuration
    file's published keys, and nothing of cfg). prompts, served: the
    sampled requests' prompt ids and the tokens the engine streamed for
    each (the cell); without them seeded prompts of `prompt_tokens`,
    and streams made here by `dispatched`, the decode program as the
    engine dispatches it (default `generate.paged_decode_chunk`; a
    control puts another here). program: (llm params, OryxConfig) the
    twin runs with, default the same (the controls differ here)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.models import oryx, qwen2
    from oryx_tpu.ops import paged_kv

    from benchmark.reference import jamba_ref as ref

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

    def chunks_of(s, ids):
        """Lane s's prefill dispatches, in order, as closures kv -> (kv,
        first token, logits [V])."""
        n = len(ids)
        emb = p_params["embed"]["weight"][jnp.asarray(ids)][None]
        emb = generate_lib.pad_embeds_for_chunks(
            emb.astype(dtype), prefill_chunk)
        out = []
        for off in range(0, n, prefill_chunk):
            end = min(off + prefill_chunk, n)

            def run(kv, off=off, end=end):
                kv, tok, _, logits = generate_lib.paged_prefill(
                    p_params, p_cfg.llm,
                    generate_lib.slice_embeds(
                        emb, jnp.asarray(off, jnp.int32),
                        width=prefill_chunk),
                    jnp.asarray([end], jnp.int32), bt[s:s + 1], kv,
                    jnp.asarray([off], jnp.int32),
                    jax.random.split(jax.random.key(0), 1), *one,
                    slots=jnp.asarray([s], jnp.int32), return_logits=True,
                    **common)
                return kv, int(np.asarray(tok)[0]), logits[0]

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

    # The twin, the way the engine runs it.
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
            *greedy, chunk=1, eos=-1, return_logits=True, **common)
        nxt = np.asarray(out[1])
        for s in np.nonzero(on)[0]:
            done[s] += 1
            twin[s].append(int(nxt[s]))
            if done[s] in keep[s]:
                got[s][done[s]] = np.asarray(out[-1][s, 0], np.float32)
        return out[0], True

    keep = [set(kept(s)[0]) | set(kept(s)[1]) for s in range(S)]
    for s, ids in enumerate(prompts):
        for run in chunks_of(s, ids):
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
    for s, ids in enumerate(prompts):
        n, stream = lens[s], np.asarray(served[s], np.int32)
        rows = ref.logits(
            params, sizes, np.concatenate([ids, stream[:-1]]),
            rows=list(range(n - 1, n - 1 + total[s])))
        ref_pick.append(np.asarray(jnp.argmax(rows, axis=-1)))
        mine = {}
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
    read["tail_over_head"] = read["tail_rms_rel"] / max(
        read["head_rms_rel"], 1e-30)
    served_ref = share(zip(served, ref_pick))
    served_twin = share(zip(served, twin))
    swapped = share(zip(served, ref_pick[1:] + ref_pick[:1])) if S > 1 else None

    def within(name):
        return bool(np.isfinite(read[name + "_rms_rel"])
                    and read[name + "_rms_rel"] <= RMS_REL_TOL
                    and read[name + "_max_rel"] <= MAX_REL_TOL)

    passed = {
        "head": within("head"), "tail": within("tail"),
        "state": state_bf16 <= STATE_BF16_MAX,
        "served": bool(served_ref >= SERVED_REF_MIN
                       and served_twin >= SERVED_TWIN_MIN),
    }
    return {
        "ok": all(passed.values()), "passed": passed, **read,
        "rms_rel_tol": RMS_REL_TOL, "max_rel_tol": MAX_REL_TOL,
        "rms_rel_by_stream": by_stream,
        "state_bf16_share": state_bf16, "state_bf16_max": STATE_BF16_MAX,
        "served_ref_agree": served_ref, "served_twin_agree": served_twin,
        "served_ref_agree_swapped": swapped,
        "served_ref_min": SERVED_REF_MIN, "served_twin_min": SERVED_TWIN_MIN,
        "served_tokens": sum(total), "slots": S,
        "prompt_tokens": lens, "stream_tokens": total,
        "rows_compared": sum(len(k) for k in keep),
    }
