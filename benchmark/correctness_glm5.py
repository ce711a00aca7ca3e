"""The comparison that decides `correct` in the learned-sparse-attention
cell (GLM-5).

WHAT IS COMPARED IS WHAT THE WINDOW SERVED (as correctness_mistral4.py,
whose shape this has): after the timed window the child hands over a
sample of the requests the engine finished in it (a cold context over
16,384 positions and a later turn over one; a later turn under it where
the configuration's `max_positions` has room), each as its prompt's
token ids and the greedy tokens the engine streamed. Three
computations run over each sampled stream, teacher-forced on the SERVED
tokens:

  - the TWIN of the served programs: the prompts go through
    `paged_prefill(return_routing=True)` in the configuration's chunks
    as lanes of one two-array pool (latents and index keys), each chunk
    handed the table width the scheduler would hand it, then through
    `paged_decode_chunk(chunk=1, return_routing=True)` fed the served
    token at every step. The twin is the same forward with logits,
    expert ids AND EVERY LAYER'S SELECTION as further outputs (a chunk's
    mask as packed bits, a decode row's indices);
  - the plain reference (reference/glm5_dsa_ref.py: float32, no cache,
    the NON-absorbed attention over the selected keys alone) FORCED to
    the twin's expert ids and to the twin's selection, over the prompt
    and the first `decode_chunks * decode_chunk` served tokens; the same
    forward returns the reference's own index scores at the compared
    rows;
  - the reference FREE (it selects and routes by itself) over the same
    positions.

What decides, each limit between bf16's reading and the nearest
control's of tools/controls_glm5.py at the published widths on the chip
(PERF.md section 6, PR 49, has every reading):

  (A) `forced`: the twin's logits against the reference handed the
      program's OWN selection and experts: rms <= FORCED_RMS_REL_TOL of
      the reference's rms and the largest difference <=
      FORCED_MAX_REL_TOL of the largest |logit|. The same function in
      two precisions: what the weights' and the cache's precision and
      every equation outside the indexer are held by.
  (B) `selection`: the selection itself, at every compared row and
      layer. The twin selected min(visible, index_topk) keys, and every
      key it selected has a reference score no lower than the
      reference's index_topk-th score less SELECT_GAP_TOL standard
      deviations of the row's scores (`select_gap`: the worst over rows
      and layers). (A) cannot see a program that selects wrongly: it is
      handed the selection.
  (C) `free`: the twin's logits against the reference's OWN selection
      and routing, rms <= FREE_RMS_REL_TOL of the reference's rms:
      looser (keys near the k-th score change sides in bf16, experts
      near the 8th too), and what holds (A) and (B) together: a
      selection that passes (B) by its scores and still reads other
      rows shows here.
  (E) `experts`: the expert layer ALONE (`qwen2._moe` with the first
      expert layer's weights, the configuration's dtype and kernels) on
      seeded rows whose selection is biased to the held experts,
      against the reference's expert layer forced to the same ids, rms
      <= EXPERT_RMS_REL_TOL. One pick in sixteen lands on a held expert
      in the cell, so the logits see the routed weights (sigmoid, the
      renormalising, the scaling factor) only faintly.
  (S) `served`: the share of each stream's first tokens that are the
      twin's own greedy tokens >= SERVED_TWIN_MIN: what holds the ENGINE
      (table cut, embed offsets, the prefix cache's splice of both
      planes, twelve lanes) to the compared programs.

Without `served` (tools/controls_glm5.py and the CPU tests, where no
engine runs) the prompts are seeded ones of `prompt_tokens` and the
streams are made here by the decode program as the engine dispatches it.
"""

from __future__ import annotations

import time

import numpy as np

# Each limit lies between two readings at the published widths on the
# chip (my chip runs, PR 49; PERF.md section 6 has every control's):
# bf16 as served, the largest over eleven comparisons at ten seeds, and
# the nearest control of tools/controls_glm5.py that must fail by it.
FORCED_RMS_REL_TOL = 4.5e-2  # bf16 1.15-1.20 %; fp8 weights 17.8 %
FORCED_MAX_REL_TOL = 6e-2  # bf16 1.09-1.24 %; fp8 weights 18.2 %
# Standard deviations of a row's scores by which the worst key the twin
# selected lies under the reference's k-th: bf16 0.070 at 6k positions,
# 0.068-0.098 at the cell's 17k; index keys in fp8 0.133 at 6k and 0.175
# at 17k (every one-line fault of the indexer 2.4-5.9). The geometric
# middle of 0.098 and 0.175.
SELECT_GAP_TOL = 0.13
# bf16 8.3 % at 6k, 12.3-13.6 % at the cell's 17k (keys at the k-th score
# and experts at the 8th change sides); softmax for sigmoid 25.4 %, fp8
# index keys 27.2 %, fp8 weights 33.6 %.
FREE_RMS_REL_TOL = 0.2
# bf16 0.342 %; fp8 weights (the shared expert's) 4.3 %, the bias in the
# weights 8.0 %: the geometric middle of 0.342 % and 4.3 %.
EXPERT_RMS_REL_TOL = 1.2e-2
EXPERT_ROWS = 512
# 0.94-1.0 over the cell's runs; no control of this tool dispatches
# another program (Mistral's, whose limit this is, read 0.06).
SERVED_TWIN_MIN = 0.5
# The reference's forwards are filled up to a multiple of this, so that
# a stream's free and forced forward share a compiled layer.
REF_PAD = 2048


def expert_layer_check(params, cfg, seed: int, *, program=None) -> float:
    """Clause (E): the relative rms difference of the first expert layer
    alone (routed and shared) with every pair live."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import oryx, qwen2

    from benchmark.reference import glm5_dsa_ref as ref

    llm = cfg.llm
    p_params, p_cfg = program or (params, cfg)
    dtype = oryx.compute_dtype(p_cfg)
    first, count = llm.held
    bias = jnp.zeros((llm.num_experts,), jnp.float32).at[
        first + jnp.arange(count)].set(1.0)
    x = jax.random.normal(
        jax.random.key(seed % (2**31 - 1)), (EXPERT_ROWS, llm.hidden_size),
        jnp.float32).astype(dtype)

    # The stacked weights go in whole and are viewed inside the program.
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


def _pack_row(idx, count: int, cols: int):
    """A decode row's selected indices (the first `count` real) as the
    packed bits of one mask row [cols]."""
    bits = np.zeros((cols * 8,), np.uint8)
    bits[np.asarray(idx[:count], np.int64)] = 1
    return np.packbits(bits)


def selection_reading(scores, packed, positions, topk: int):
    """Clause (B) on one stream: scores [L, R, T] the reference's own
    index scores of the compared rows, packed [L][rows, cols] the twin's
    selection, positions [R] the rows' positions. Returns (worst gap in
    standard deviations of a row's scores, the share of the twin's keys
    that are among the reference's top k, rows whose count is wrong)."""
    gap, agree, keys, miscounted = 0.0, 0, 0, 0
    for l in range(scores.shape[0]):
        for r, t in enumerate(positions):
            row = np.asarray(scores[l, r, : t + 1], np.float64)
            sel = np.unpackbits(packed[l][t])[: t + 1].astype(bool)
            k = min(t + 1, topk)
            miscounted += int(sel.sum() != k)
            if not sel.any() or t + 1 <= topk:
                continue
            kth = np.partition(row, -k)[-k]
            gap = max(gap, float((kth - row[sel].min())
                                 / max(row.std(), 1e-30)))
            agree += int((row[sel] >= kth).sum())
            keys += int(sel.sum())
    return gap, agree / max(1, keys), miscounted


def logit_check(params, cfg, seed: int, *, page_size: int,
                prefill_chunk: int, decode_chunk: int, max_ctx: int,
                prompt_tokens=(6000, 300), decode_chunks: int = 2,
                prompts=None, served=None, program=None,
                dispatched=None) -> dict:
    """params/cfg: what the reference computes with (the llm subtree and
    OryxConfig). prompts, served: the sampled requests' prompt ids and
    the tokens the engine streamed for each (the cell); without them
    seeded prompts of `prompt_tokens`, and streams made here by
    `dispatched` (default `generate.paged_decode_chunk`; a control puts
    another here). program: (llm params, OryxConfig) the twin runs with,
    default the same (the controls differ here). max_ctx: the engine's,
    which fixes the widths a prefill chunk's table is cut to."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate as generate_lib
    from oryx_tpu.models import oryx, qwen2
    from oryx_tpu.serve import scheduler

    from benchmark.reference import glm5_dsa_ref as ref

    llm = cfg.llm
    # Where the comparison's seconds go, part by part (every part ends
    # in values read back to the host): a first run on a machine pays
    # each part's compiles, a later one does not. Not compared.
    parts, t_part = {}, time.monotonic()

    def part(name):
        nonlocal t_part
        now = time.monotonic()
        parts[name] = parts.get(name, 0.0) + now - t_part
        t_part = now

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
    L, topk = llm.num_layers, llm.index_topk
    maxp = max_ctx // page_size
    widths = scheduler.prefill_table_buckets(maxp, page_size)
    assert max(lens) + steps + 1 <= max_ctx
    # A lane holds the pages its stream reaches (its last prefill chunk's
    # padding included), the rest of its table is the sentinel.
    need = [-(-(-(-n // prefill_chunk) * prefill_chunk + steps + 1)
              // page_size) for n in lens]
    need = [min(n, maxp) for n in need]
    P = sum(need)
    kv = qwen2.init_paged_kv_cache(p_cfg.llm, P, page_size, dtype=dtype)
    bt = np.full((S, maxp), P, np.int32)
    for s, n in enumerate(need):
        bt[s, :n] = sum(need[:s]) + np.arange(n)
    bt = jnp.asarray(bt)
    one = (jnp.zeros((1,), jnp.float32), jnp.ones((1,), jnp.float32),
           jnp.zeros((1,), jnp.int32))
    greedy = (jnp.zeros((S,), jnp.float32), jnp.ones((S,), jnp.float32),
              jnp.zeros((S,), jnp.int32))

    routed = [[] for _ in range(S)]  # the twin's expert ids [Lm, rows, K]
    got = [[] for _ in range(S)]  # the twin's logits, row by row
    twin = [[] for _ in range(S)]  # the twin's own greedy tokens
    cols = [-(-(n + steps) // 8) for n in lens]
    picked = [[np.zeros((n + steps, c), np.uint8) for _ in range(L)]
              for n, c in zip(lens, cols)]  # the twin's selection, packed
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
            sel = np.asarray(routing["selected"])[:, 0, : end - off]
            w = min(cols[s], sel.shape[-1])
            for l in range(L):
                picked[s][l][off:end, :w] = sel[l, :, :w]
        got[s].append(np.asarray(routing["logits"], np.float32)[0])
        twin[s].append(int(np.asarray(tok)[0]))
    part("twin_prefill")

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
        part("streams_made_here")
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
        nxt, logits = np.asarray(out[1]), np.asarray(out[-3], np.float32)
        ids, sel = np.asarray(out[-2]), np.asarray(out[-1])  # [1, L, S, .]
        for s in range(S):
            twin[s].append(int(nxt[s]))  # what the twin would feed next
            got[s].append(logits[s, 0])
            routed[s].append(np.moveaxis(ids[:, :, s], 0, 1))
            t = lens[s] + k
            for l in range(L):
                picked[s][l][t] = _pack_row(
                    sel[0, l, s], min(t + 1, sel.shape[-1]), cols[s])
    del kv
    part("twin_decode")

    sq = {"forced": 0.0, "free": 0.0, "ref": 0.0}
    worst = {"forced": 0.0, "free": 0.0}
    by_prompt, absmax, compared = {}, 0.0, 0
    gap, agree, miscounted = 0.0, [], 0
    sets_same = sets = 0
    for s, ids in enumerate(prompts):
        n, stream = len(ids), np.asarray(served[s], np.int32)
        prog = np.concatenate(routed[s], axis=1)  # [Lm, n + steps, K]
        lg = np.stack(got[s])  # [steps + 1, V]
        rows = list(range(n - 1, n + steps))
        given = np.concatenate([ids, stream[:steps]])
        # (A) and (B): forced to the twin's experts and selection.
        want, scores = ref.logits(
            params, llm, given, rows=rows, forced_experts=prog,
            forced_selection=picked[s], score_rows=rows, pad_to=REF_PAD)
        want = np.asarray(want)
        g, a, m = selection_reading(np.asarray(scores), picked[s], rows, topk)
        gap, miscounted = max(gap, g), miscounted + m
        agree.append(a)
        absmax = max(absmax, float(np.max(np.abs(want))))
        r2 = float(np.sum(np.square(want, dtype=np.float64)))
        d2 = float(np.sum(np.square(lg - want, dtype=np.float64)))
        sq["ref"] += r2
        sq["forced"] += d2
        worst["forced"] = max(worst["forced"],
                              float(np.max(np.abs(lg - want))))
        by_prompt[str(n)] = float(np.sqrt(d2 / max(r2, 1e-30)))
        compared += steps + 1
        del want, scores
        part("reference_forced")
        # (C): the reference by itself.
        free, chosen = ref.logits(
            params, llm, given, rows=rows, return_experts=True,
            pad_to=REF_PAD)
        free = np.asarray(free)
        same = np.all(np.sort(prog, -1) == np.sort(
            np.asarray(chosen)[:, : n + steps], -1), axis=-1)
        sets_same += int(same.sum())
        sets += same.size
        sq["free"] += float(np.sum(np.square(lg - free, dtype=np.float64)))
        worst["free"] = max(worst["free"], float(np.max(np.abs(lg - free))))
        del free, chosen
        part("reference_free")
    rms = {k: float(np.sqrt(v / max(1, compared * llm.vocab_size)))
           for k, v in sq.items()}
    expert_rms_rel = expert_layer_check(params, cfg, seed, program=program)
    part("expert_layer")

    hit = total = 0
    for a, b in zip(served, twin):
        m = min(len(a), len(b))
        hit += int(np.sum(np.asarray(a[:m]) == np.asarray(b[:m])))
        total += m
    served_twin = hit / max(1, total)
    passed = {
        "forced": bool(np.isfinite(rms["forced"])
                       and rms["forced"] <= FORCED_RMS_REL_TOL * rms["ref"]
                       and worst["forced"] <= FORCED_MAX_REL_TOL * absmax),
        "selection": bool(miscounted == 0 and gap <= SELECT_GAP_TOL),
        "free": bool(np.isfinite(rms["free"])
                     and rms["free"] <= FREE_RMS_REL_TOL * rms["ref"]),
        "experts": bool(np.isfinite(expert_rms_rel)
                        and expert_rms_rel <= EXPERT_RMS_REL_TOL),
        "served": served_twin >= SERVED_TWIN_MIN,
    }
    return {
        "ok": all(passed.values()), "passed": passed,
        "forced_rms_rel": rms["forced"] / max(rms["ref"], 1e-30),
        "forced_max_rel": worst["forced"] / max(absmax, 1e-30),
        "forced_rms_rel_by_prompt": by_prompt,
        "select_gap": gap, "select_agree": float(np.mean(agree)),
        "select_miscounted_rows": miscounted,
        "free_rms_rel": rms["free"] / max(rms["ref"], 1e-30),
        "free_max_rel": worst["free"] / max(absmax, 1e-30),
        "routing_agree": sets_same / max(1, sets),
        "expert_rms_rel": expert_rms_rel,
        "served_twin_agree": served_twin,
        "served_tokens": sum(len(t) for t in served),
        "ref_absmax": absmax, "ref_rms": rms["ref"],
        "positions": compared, "slots": S, "decode_steps": steps,
        "prompt_tokens": lens, "table_positions": sorted(used),
        "seconds_by_part": parts,
    }
