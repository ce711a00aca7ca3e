"""Block mode's deferred commit, at `sdar_tiny` on the CPU: block n's
final tokens go on to dispatch n+1, whose first forward carries them as
commit lanes before the opening block (`generate.paged_block_step`'s
`pending` / `pending_live`). Held to the forward-by-forward schedule
with an explicit commit forward (`generate.paged_block_forward`): the
tokens, the K/V the pages hold afterwards, the mutations that must
break both, and the benchmark's comparison driven so that it runs the
commit lanes."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, oryx, qwen2

F32 = jnp.float32
PAGE, PAGES_A_SLOT, BLOCKS = 16, 4, 5
# Prompts of three slots: tails (len % B) of 0, 1 and 3.
PROMPT_LENGTHS = (20, 13, 7)

# (remasking, T, the middle slot's temperature)
RULES = [
    pytest.param("low_confidence_static", 2, 0.0, id="static"),
    pytest.param("low_confidence_dynamic", 4, 0.0, id="dynamic"),
    pytest.param("low_confidence_static", 2, 0.8, id="one_sampled_row"),
]
THRESHOLD = 1.0 / 400  # near 1 / vocabulary: the dynamic rule takes several


@pytest.fixture(scope="module")
def model():
    """Four times the init's scale: at 0.02 every greedy block is one
    token repeated whatever its context, and a block generated over an
    uncommitted one would show nothing."""
    cfg = cfg_lib.sdar_tiny().llm
    params = qwen2.init_params(cfg, jax.random.key(0))
    return cfg, jax.tree.map(lambda x: x * 4 if x.ndim >= 2 else x, params)


def _prefilled(cfg, params):
    """Three slots' prompts, their whole blocks prefilled into pages of
    their own: (kv, block tables, lengths, first blocks, n_known)."""
    B, S = cfg.block_length, len(PROMPT_LENGTHS)
    kv = qwen2.init_paged_kv_cache(cfg, S * PAGES_A_SLOT, PAGE, dtype=F32)
    bt = jnp.arange(S * PAGES_A_SLOT, dtype=jnp.int32).reshape(S, -1)
    one = (jnp.zeros((1,)), jnp.ones((1,)), jnp.zeros((1,), jnp.int32))
    rng = np.random.default_rng(7)
    blk, known = np.zeros((S, B), np.int32), np.zeros((S,), np.int32)
    lengths = np.zeros((S,), np.int32)
    for s, n in enumerate(PROMPT_LENGTHS):
        ids = rng.integers(3, 500, n)
        head = n - n % B
        emb = params["embed"]["weight"][
            jnp.asarray(np.pad(ids[:head], (0, 32 - head)))][None]
        kv, _, _ = generate.paged_prefill(
            params, cfg, emb, jnp.asarray([head], jnp.int32), bt[s:s + 1],
            kv, jnp.asarray([0], jnp.int32),
            jax.random.split(jax.random.key(0), 1), *one)
        blk[s, :n - head], known[s], lengths[s] = ids[head:], n - head, head
    return kv, bt, lengths, blk, known


def _sampling(temp):
    S = len(PROMPT_LENGTHS)
    temps = np.zeros((S,), np.float32)
    temps[1] = temp
    return (jnp.asarray(temps), jnp.full((S,), 0.9, F32),
            jnp.zeros((S,), jnp.int32))


def _by_forwards(cfg, params, remasking, steps, temp):
    """The schedule the deferred commit replaces, forward by forward on
    the host: denoising forwards until no mask is left, then ONE commit
    forward over the block's final tokens, then the next block. The
    keys split as `paged_block_step` splits them. Returns (tokens
    [BLOCKS, S, B], kv, block tables, lengths after the last block)."""
    B = cfg.block_length
    kv, bt, lengths, blk, known = _prefilled(cfg, params)
    S = len(lengths)
    sampling = _sampling(temp)
    keys = jax.random.split(jax.random.key(5), S)
    live = jnp.ones((S,), bool)
    max_steps = steps if remasking == "low_confidence_static" else B
    out = []
    for _ in range(BLOCKS):
        masked = np.arange(B)[None, :] >= known[:, None]
        blk = np.where(masked, cfg.mask_token_id, blk).astype(np.int32)
        t = 0
        while t < max_steps and masked.any():
            lg, kv, _ = generate.paged_block_forward(
                params, cfg, kv, bt, jnp.asarray(blk), jnp.asarray(lengths),
                live)
            pair = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
            lane_keys = jax.vmap(lambda k: jax.random.split(k, B))(
                pair[:, 1]).reshape(S * B)
            keys = pair[:, 0]
            x0 = generate.sample_token_rows(lg, lane_keys, **{
                k: jnp.repeat(a, B) for k, a in zip(
                    ("temperature", "top_p", "top_k"), sampling)})
            conf = jnp.exp(
                jnp.take_along_axis(lg, x0[:, None], axis=-1)[:, 0]
                - jax.nn.logsumexp(lg, axis=-1))
            fix = np.asarray(generate.block_unmask(
                jnp.asarray(masked), conf.reshape(S, B),
                jnp.asarray(t, jnp.int32), steps=steps, remasking=remasking,
                threshold=THRESHOLD))
            blk = np.where(fix, np.asarray(x0).reshape(S, B), blk)
            masked, t = masked & ~fix, t + 1
        _, kv, _ = generate.paged_block_forward(  # the commit
            params, cfg, kv, bt, jnp.asarray(blk), jnp.asarray(lengths), live)
        out.append(blk.copy())
        lengths = lengths + B
        blk, known = np.zeros_like(blk), np.zeros_like(known)
    return np.stack(out), kv, bt, lengths


def _deferred(cfg, params, remasking, steps, temp, mutation=None):
    """The same blocks through `paged_block_step`, every block's tokens
    handed on to the next dispatch as they lie on the device."""
    B = cfg.block_length
    kv, bt, lengths, blk, known = _prefilled(cfg, params)
    S = len(lengths)
    keys = jax.random.split(jax.random.key(5), S)
    toks, pending = jnp.zeros((S, B), jnp.int32), jnp.zeros((S,), bool)
    out, counted = [], []
    for _ in range(BLOCKS):
        handed = toks
        if mutation == "write_mask_off":
            pending = jnp.zeros((S,), bool)
        elif mutation == "another_slots_tokens":
            handed = jnp.roll(toks, 1, axis=0)
        kv, toks, _, lengths, _, keys, counts = generate.paged_block_step(
            params, cfg, kv, bt, jnp.asarray(blk), jnp.asarray(known),
            jnp.asarray(lengths), jnp.zeros((S,), bool), keys,
            *_sampling(temp), handed, pending,
            steps=steps, remasking=remasking, threshold=THRESHOLD, eos=-1)
        pending = jnp.ones((S,), bool)
        out.append(np.asarray(toks))
        counted.append(counts)
        blk, known = np.zeros_like(blk), np.zeros_like(known)
    return np.stack(out), kv, bt, np.asarray(lengths), counted


def _stream_kv(kv, bt, slot, upto):
    """Slot `slot`'s K and V at logical positions 0..upto-1: [2, L, upto, ...]."""
    pos = np.arange(upto)
    pages = np.asarray(bt)[slot, pos // PAGE]
    return np.stack([
        np.asarray(kv[plane])[:, pages, pos % PAGE] for plane in ("k", "v")])


@pytest.mark.parametrize("remasking,steps,temp", RULES)
def test_deferred_commit_gives_the_forward_by_forward_tokens_and_pages(
        model, remasking, steps, temp):
    """Five blocks over three slots with prompt tails 0, 1 and 3: the
    tokens are the explicit schedule's, token for token, and for every
    block but the last the pages hold the K/V a stand-alone commit
    forward writes. The last block was never committed (nothing reads
    it): its positions hold what its last denoising forward left."""
    cfg, params = model
    B = cfg.block_length
    want, kv_w, bt, lengths = _by_forwards(cfg, params, remasking, steps, temp)
    got, kv_g, _, lengths_g, counts = _deferred(
        cfg, params, remasking, steps, temp)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(lengths_g, lengths)
    # Slots tell their blocks apart, and blocks differ: a slot that read
    # another's pages, or a block read uncommitted, would show.
    assert len({tuple(b) for b in want.reshape(-1, B)}) > BLOCKS
    for s in range(len(PROMPT_LENGTHS)):
        done = int(lengths[s]) - B
        np.testing.assert_allclose(
            _stream_kv(kv_g, bt, s, done), _stream_kv(kv_w, bt, s, done),
            atol=1e-5, rtol=1e-5)
        last_g = _stream_kv(kv_g, bt, s, done + B)[:, :, done:]
        last_w = _stream_kv(kv_w, bt, s, done + B)[:, :, done:]
        assert np.abs(last_g - last_w).max() > 1e-3
    # T forwards a block and no other: a slot takes part once a forward.
    for n, c in enumerate(counts):
        stats = dict(zip(generate.BLOCK_STATS, (int(x) for x in c["stats"])))
        assert stats["forwards"] <= (
            steps if remasking == "low_confidence_static" else B)
        assert list(np.asarray(c["slot_forwards"])) <= [stats["forwards"]] * 3
        if remasking == "low_confidence_static" and n:
            assert stats["forwards"] == steps
            assert list(np.asarray(c["slot_forwards"])) == [steps] * 3
            # The first forward carries 2B lanes a slot, the others B.
            assert stats["moe_rows_routed"] == (
                (steps + 1) * 3 * B * cfg.num_layers
                * cfg.num_experts_per_tok)


@pytest.mark.parametrize("mutation", ["write_mask_off", "another_slots_tokens"])
def test_broken_commit_lanes_change_the_tokens_from_the_second_block_on(
        model, mutation):
    """Commit lanes that write nothing, or write another slot's block:
    the first block (which has nothing pending) is the explicit
    schedule's, the second is not, and neither are the pages."""
    cfg, params = model
    B = cfg.block_length
    rule = ("low_confidence_static", 2, 0.0)
    want, kv_w, bt, lengths = _by_forwards(cfg, params, *rule)
    got, kv_g, *_ = _deferred(cfg, params, *rule, mutation=mutation)
    np.testing.assert_array_equal(got[0], want[0])
    assert (got[1] != want[1]).any()
    assert (got[1:] != want[1:]).any(axis=(1, 2)).sum() >= BLOCKS - 2
    first = [n - n % B for n in PROMPT_LENGTHS]
    assert any(
        np.abs(_stream_kv(kv_g, bt, s, first[s] + B)[:, :, first[s]:]
               - _stream_kv(kv_w, bt, s, first[s] + B)[:, :, first[s]:]
               ).max() > 1e-3
        for s in range(len(first)))


def test_without_a_pending_block_the_step_commits_nothing(model):
    """Called as the benchmark's comparison calls it (no `pending`), no
    forward carries commit lanes: the open block's positions alone are
    written, and the pages before `lengths` are untouched. Handed a
    pending block that is live nowhere it does the same, a slot at
    length 0 (commit lanes under position 0) included."""
    cfg, params = model
    B = cfg.block_length
    kv, bt, lengths, blk, known = _prefilled(cfg, params)
    lengths = lengths.copy()
    lengths[2] = 0  # as an empty prompt would leave it
    S = len(lengths)
    got = []
    for dead in ((), (jnp.full((S, B), 7, jnp.int32), jnp.zeros((S,), bool))):
        before = {k: np.asarray(v).copy() for k, v in kv.items()}
        kv, toks, n_new, after, _, _, counts = generate.paged_block_step(
            params, cfg, kv, bt, jnp.asarray(blk), jnp.asarray(known),
            jnp.asarray(lengths), jnp.zeros((S,), bool),
            jax.random.split(jax.random.key(5), S), *_sampling(0.0), *dead,
            steps=2, remasking="low_confidence_static", threshold=0.9,
            eos=-1)
        assert list(np.asarray(after)) == [int(n) + B for n in lengths]
        for s in range(S):
            np.testing.assert_array_equal(
                _stream_kv(kv, bt, s, int(lengths[s])),
                _stream_kv(before, bt, s, int(lengths[s])))
        got.append((np.asarray(toks), int(counts["stats"][2])))
    np.testing.assert_array_equal(got[0][0], got[1][0])
    # Dead commit lanes are routed and counted like a finished slot's.
    pairs = S * B * cfg.num_layers * cfg.num_experts_per_tok
    assert (got[0][1], got[1][1]) == (2 * pairs, 3 * pairs)


# --------------------------------------------------------------------------
# the benchmark's comparison, made to run the commit lanes
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def check_model():
    """The model `tests/benchmark/test_bench_rehearsal_blockdiff.py`
    holds the comparison to (experts and router times ten)."""
    cfg = cfg_lib.sdar_tiny()
    cfg = dataclasses.replace(cfg, generation=dataclasses.replace(
        cfg.generation, denoising_steps=2,
        remasking="low_confidence_static"))
    params = oryx.init_params(cfg, jax.random.key(3))["llm"]
    params = dict(params, layers=dict(params["layers"]))
    for name in ("experts", "router"):
        params["layers"][name] = jax.tree.map(
            lambda a: a * 10.0, params["layers"][name])
    return cfg, params


def _handing_on(mutation=None):
    """`paged_block_step` as the engine calls it: every call hands the
    call before's tokens on as the pending block of every slot."""
    last = []

    def step(p, c, kv, bt, blk, n_known, lengths, finished, *rest, **kw):
        S = blk.shape[0]
        pending = last[0] if last else jnp.zeros_like(blk)
        if mutation == "other_tokens":
            pending = (pending + 1) % 500
        out = generate.paged_block_step(
            p, c, kv, bt, blk, n_known, lengths, finished, *rest,
            pending, jnp.full((S,), bool(last)), **kw)
        last[:] = [out[1]]
        return out

    return step


@pytest.mark.parametrize("mutation", [None, "other_tokens"])
def test_the_comparison_runs_the_commit_lanes_when_the_blocks_are_handed_on(
        check_model, mutation):
    """`block_logit_check` as the rehearsal runs it passes the program
    as called without a pending block (it recommits every block itself,
    forward by forward). Given a step that hands each block's tokens on
    as the engine does, the SAME unedited comparison runs the fused
    forward: it passes it, and fails commit lanes that write other
    tokens' K/V over the block it had committed (the forward-by-forward
    pass then reads those pages too, so it is the logits against the
    reference, the `forced` clause, that see it)."""
    from benchmark import correctness_sdar

    cfg, params = check_model
    programs = (generate.paged_prefill, generate.paged_block_forward,
                _handing_on(mutation))
    out = correctness_sdar.block_logit_check(
        params, cfg, 5, page_size=16, prefill_chunk=32, prompt_tokens=62,
        blocks=3, programs=programs)
    if mutation is None:
        plain = correctness_sdar.block_logit_check(
            params, cfg, 5, page_size=16, prefill_chunk=32, prompt_tokens=62,
            blocks=3)
        assert plain["ok"] and out["ok"], (plain["passed"], out["passed"])
        assert out["step_tokens_agree"] == out["step_tokens"] == 4 * 12 - 6
        assert out["forced_logit_rms_diff"] == plain["forced_logit_rms_diff"]
    else:
        assert not out["ok"] and not out["passed"]["forced"], out
