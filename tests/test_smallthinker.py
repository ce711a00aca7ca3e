"""SmallThinker-class decoder (window layers beside global layers
without positions, ReGLU experts routed from the layer's input):
`qwen2.forward` and the two split step programs through BOTH paged
planes against benchmark/reference/smallthinker_ref.py, on seeded
weights at `config.smallthinker_tiny()`, logits not tokens.

Tolerances: everything here is float32 at matmul precision "highest"
(conftest), the reference too, so the two differ by summation order
alone: 2e-3 of the largest |logit| (~3 at weights x 4) is ~50x what a
run reads (1e-4) and 100x under what any planted fault reads (a window
off by one token moves a logit by 0.05 and more)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import smallthinker_ref as ref
from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, qwen2
from oryx_tpu.ops import paged_kv

TOL = 2e-3
PAGE, CHUNK, DECODE = 8, 16, 4


def keys_of(llm) -> dict:
    """The source's keys for a program config (the tiny preset has no
    configuration file)."""
    per, off = llm.global_layer_period, llm.global_layer_offset
    lay = [0 if i % per == off else 1 for i in range(llm.num_layers)]
    return dict(
        num_hidden_layers=llm.num_layers, num_attention_heads=llm.num_heads,
        num_key_value_heads=llm.num_kv_heads, head_dim=llm.head_dim,
        rms_norm_eps=llm.rms_norm_eps, rope_theta=llm.rope_theta,
        sliding_window_size=llm.sliding_window, sliding_window_layout=lay,
        rope_layout=lay, moe_num_primary_experts=llm.num_experts,
        moe_num_active_primary_experts=llm.num_experts_per_tok,
        norm_topk_prob=llm.norm_topk_prob)


@pytest.fixture(scope="module")
def model():
    llm = cfg_lib.smallthinker_tiny().llm
    params = qwen2.init_params(llm, jax.random.key(0))
    # x 4: at 0.02 every logit is ~0 and any fault hides.
    params = jax.tree_util.tree_map(
        lambda a: a * 4 if a.ndim >= 2 else a, params)
    return llm, params, ref.sizes_from_keys(keys_of(llm))


class Lanes:
    """The two planes' host state and the two programs, stepped by hand
    the way the engine steps them (the window tables handed over as
    COPIES: `advance` shifts them in place while a dispatch may still
    be reading): a global allocator and table, a
    `WindowPlane`, freed window pages poisoned with NaN at once (and
    zeroed when handed out again, as a fresh pool's are)."""

    def __init__(self, llm, params, slots: int, max_ctx: int,
                 impl: str = "xla", window_pages: int | None = None):
        self.llm, self.params, self.impl = llm, params, impl
        self.S, self.maxp = slots, max_ctx // PAGE
        self.Tw = paged_kv.window_table_pages(
            llm.sliding_window, max(CHUNK, DECODE), PAGE)
        Pw = window_pages or slots * self.Tw
        self.galloc = paged_kv.PageAllocator(slots * self.maxp, PAGE)
        self.bt = np.full((slots, self.maxp), self.galloc.sentinel, np.int32)
        self.win = paged_kv.WindowPlane(
            Pw, PAGE, slots, self.Tw, llm.sliding_window)
        self.kv = qwen2.init_paged_kv_cache(
            llm, (slots * self.maxp, Pw), PAGE, dtype=jnp.float32)
        self.lengths = np.zeros(slots, np.int32)
        self.released = 0
        self.greedy = (jnp.zeros((slots,), jnp.float32),
                       jnp.ones((slots,), jnp.float32),
                       jnp.zeros((slots,), jnp.int32))

    def _poison(self, pages, value):
        if pages:
            idx = jnp.asarray(pages)
            for n in paged_kv.WINDOW_PLANES:
                self.kv[n] = self.kv[n].at[:, idx].set(value)

    def _cover(self, s, first_query, tokens):
        held = int((self.bt[s] != self.galloc.sentinel).sum())
        need = self.galloc.pages_for(tokens) - held
        if need > 0:
            self.bt[s, held:held + need] = self.galloc.alloc(need)
        freed = self.win.advance(s, first_query)
        self.released += len(freed)
        self._poison(freed, jnp.nan)
        if freed:  # poisoned before the next step: a read would show
            assert bool(jnp.isnan(self.kv["wv"][:, jnp.asarray(freed)]).all())
        before = set(self.win.held(s))
        assert self.win.grow(s, tokens)
        self._poison(sorted(set(self.win.held(s)) - before), 0.0)
        # The acceptance rule: no window page wholly older than
        # n - W - chunk, and the table starts at the base.
        assert int(self.win.base[s]) + PAGE > first_query - \
            self.llm.sliding_window + 1
        assert len(self.win.held(s)) <= self.Tw

    def prefill(self, s, ids):
        """Prompt `ids` into lane s, chunk by chunk; the logits of its
        last token."""
        emb = self.params["embed"]["weight"][jnp.asarray(ids)][None]
        emb = generate.pad_embeds_for_chunks(emb, CHUNK)
        n = len(ids)
        for off in range(0, n, CHUNK):
            end = min(off + CHUNK, n)
            self._cover(s, off, end)
            self.kv, tok, _, routing = generate.paged_prefill(
                self.params, self.llm,
                generate.slice_embeds(emb, jnp.asarray(off), width=CHUNK),
                jnp.asarray([end], jnp.int32), jnp.asarray(self.bt[s:s + 1]),
                self.kv, jnp.asarray([off], jnp.int32),
                jax.random.split(jax.random.key(0), 1),
                jnp.zeros((1,)), jnp.ones((1,)), jnp.zeros((1,), jnp.int32),
                attn_impl=self.impl, return_routing=True,
                window_tables=jnp.asarray(self.win.tables[s:s + 1].copy()),
                window_base=jnp.asarray(self.win.base[s:s + 1].copy()),
            )
        self.lengths[s] = n
        return np.asarray(routing["logits"][0]), int(np.asarray(tok)[0])

    def decode(self, toks, live):
        """One decode chunk: lane s is fed toks[s] first and its own
        greedy tokens after; [S, DECODE, V] logits, [S, DECODE] the
        tokens fed."""
        for s in np.nonzero(live)[0]:
            self._cover(s, int(self.lengths[s]), int(self.lengths[s]) + DECODE)
        out = generate.paged_decode_chunk(
            self.params, self.llm, self.kv, jnp.asarray(self.bt),
            jnp.asarray(toks, jnp.int32), jnp.asarray(self.lengths),
            ~jnp.asarray(live), jnp.zeros((self.S, 0), jnp.int32),
            jax.random.split(jax.random.key(1), self.S), *self.greedy,
            chunk=DECODE, eos=-1, attn_impl=self.impl, return_routing=True,
            window_tables=jnp.asarray(self.win.tables.copy()),
            window_base=jnp.asarray(self.win.base.copy()),
        )
        self.kv = out[0]
        self.lengths = np.array(out[2])
        return np.asarray(out[-2]), np.asarray(out[6]), np.asarray(out[1])


def run_stream(model, prompt_len: int, chunks: int, impl: str = "xla",
               seed: int = 0):
    """One lane: prefill, then `chunks` decode chunks; the program's
    logits at every row from the prompt's last against the reference's
    full forward over the same tokens."""
    llm, params, sizes = model
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, llm.vocab_size, prompt_len)
    lanes = Lanes(llm, params, 1, 512, impl)
    first, tok = lanes.prefill(0, ids)
    got, fed = [first], []
    for _ in range(chunks):
        logits, toks, nxt = lanes.decode([tok], np.array([True]))
        got += list(logits[0])
        fed += list(toks[0])
        tok = int(nxt[0])
    stream = np.concatenate([ids, fed])
    want = np.asarray(ref.logits(
        params, sizes, stream, rows=list(range(prompt_len - 1, len(stream)))))
    got = np.stack(got)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    return got, want, lanes


@pytest.mark.parametrize("prompt_len,chunks,released", [
    (12, 3, False),    # (a) never reaches the window of 32
    (25, 6, True),     # (b) crosses it during decode
    (90, 10, True),    # (c) passes it inside a prefill chunk, then > 2 W
])
def test_both_planes_against_the_reference(model, prompt_len, chunks,
                                           released):
    got, want, lanes = run_stream(model, prompt_len, chunks)
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()
    assert (lanes.released > 0) == released
    n, W = int(lanes.lengths[0]), model[0].sliding_window
    # From the allocator: the lane holds what covers its live window
    # and nothing older; the global plane holds every page.
    held = lanes.win.held(0)
    assert lanes.win.allocator.num_free == \
        lanes.win.allocator.num_pages - len(held)
    assert int(lanes.win.base[0]) >= max(0, n - DECODE - W - PAGE)
    assert len(held) <= lanes.Tw
    assert int((lanes.bt[0] != lanes.galloc.sentinel).sum()) == -(-n // PAGE)
    lanes.win.check_invariant()


def test_pallas_kernels_walk_the_same_window(model):
    """The Pallas twins (interpret mode): the paged kernel's first
    visible position a row and the flash kernel's lower bound."""
    got, want, _ = run_stream(model, 45, 4, impl="pallas", seed=3)
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


def test_two_lanes_of_different_kinds_in_one_chunk(model):
    """A lane far past the window beside one that never reached it, in
    the same decode chunks, each against its own stream's reference."""
    llm, params, sizes = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, llm.vocab_size, n) for n in (70, 9)]
    lanes = Lanes(llm, params, 3, 256)  # slot 1 stays empty
    heads, toks = {}, [0, 0, 0]
    for s, ids in zip((0, 2), prompts):
        heads[s], toks[s] = lanes.prefill(s, ids)
    live = np.array([True, False, True])
    got = {s: [heads[s]] for s in (0, 2)}
    fed = {0: [], 2: []}
    for _ in range(3):
        logits, t, nxt = lanes.decode(toks, live)
        for s in (0, 2):
            got[s] += list(logits[s])
            fed[s] += list(t[s])
            toks[s] = int(nxt[s])
    assert lanes.released > 0 and int(lanes.win.base[2]) == 0
    for s, ids in zip((0, 2), prompts):
        stream = np.concatenate([ids, fed[s]])
        want = np.asarray(ref.logits(
            params, sizes, stream,
            rows=list(range(len(ids) - 1, len(stream)))))
        assert np.abs(np.stack(got[s]) - want).max() <= TOL * np.abs(want).max()


def test_a_stale_window_table_shows(model):
    """The poison works: a table that is NOT shifted after the release
    reads a freed page and the logits are not finite."""
    llm, params, _ = model
    lanes = Lanes(llm, params, 1, 256)
    _, tok = lanes.prefill(0, np.random.default_rng(1).integers(3, 500, 60))
    stale = lanes.win.tables.copy(), lanes.win.base.copy()
    lanes.decode([tok], np.array([True]))
    lanes.decode([tok], np.array([True]))
    assert lanes.released
    freed = set(stale[0][0]) - set(lanes.win.tables[0])
    assert freed
    out = generate.paged_decode_chunk(
        params, llm, lanes.kv, jnp.asarray(lanes.bt), jnp.asarray([tok]),
        jnp.asarray(lanes.lengths), jnp.asarray([False]),
        jnp.zeros((1, 0), jnp.int32), jax.random.split(jax.random.key(1), 1),
        *lanes.greedy, chunk=1, eos=-1, return_routing=True,
        window_tables=jnp.asarray(stale[0]), window_base=jnp.asarray(stale[1]))
    assert not np.isfinite(np.asarray(out[-2])).all()


def test_no_cache_forward_and_params(model):
    llm, params, sizes = model
    ids = np.random.default_rng(2).integers(3, llm.vocab_size, 80)
    want = np.asarray(ref.logits(params, sizes, ids))
    for impl in ("xla", "pallas"):
        got, _ = qwen2.forward(
            params, llm, input_ids=jnp.asarray(ids)[None], attn_impl=impl)
        assert np.abs(np.asarray(got[0]) - want).max() <= TOL * np.abs(want).max()
    # The published preset's count by the program, at the cell's depth.
    big = dataclasses.replace(cfg_lib.smallthinker_21b().llm, num_layers=8)
    shapes = jax.eval_shape(
        lambda k: qwen2.init_params(big, k, jnp.bfloat16), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_966_937_600


@pytest.mark.parametrize("kwargs,words", [
    (dict(block_length=4), "diffusion over blocks"),
    (dict(kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=8, moe_activation="silu",
          router_input="post_attn"), "latent attention"),
    (dict(attn_layer_period=4, attn_layer_offset=1, mamba_dt_rank=4,
          use_rope=False, num_experts=0, num_experts_per_tok=0,
          moe_activation="silu", router_input="post_attn"),
     "state-space layers"),
])
def test_config_refuses_by_name(kwargs, words):
    llm = cfg_lib.smallthinker_tiny().llm
    with pytest.raises(ValueError, match="window layers.*" + words):
        dataclasses.replace(llm, **kwargs)


def test_config_refuses_a_mesh_and_a_one_plane_pool():
    cfg = cfg_lib.smallthinker_tiny()
    with pytest.raises(ValueError, match="window layers.*a mesh"):
        dataclasses.replace(cfg, mesh=cfg_lib.MeshConfig(tp=2))
    with pytest.raises(ValueError, match="window layers.*ring"):
        dataclasses.replace(cfg, attn_impl="ring")
    with pytest.raises(ValueError, match="global pages, window pages"):
        qwen2.init_paged_kv_cache(cfg.llm, 8, 8)
    with pytest.raises(ValueError, match="window layers.*kv_dtype"):
        qwen2.init_paged_kv_cache(cfg.llm, (8, 8), 8, kv_dtype="int8")
    with pytest.raises(ValueError, match="need window layers"):
        dataclasses.replace(cfg_lib.oryx_tiny().llm, global_layer_period=4)
