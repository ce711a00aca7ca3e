"""The decode step's two kernels (`ops/pallas/ssm_step.py`, through
`mamba.mixer_step_inplace`) in interpret mode on the CPU against
`mamba.mixer_step` on the layer's rows, which they replace under
`attn_impl="pallas"` and which stays every other path's arithmetic,
over lanes (one, a pool the conv's grid takes whole, the engine's 64 in
blocks of 16 and more live lanes than states in flight), `live`
patterns and channel-tile counts. Outputs to float32 rounding; a dead
lane's conv rows and state BIT-equal; the state float32 by the rule the
cell's comparison reads it with; layer li's rows the only ones of the
planes that change; a shape the tiles do not fit keeps `mixer_step`.
The Mosaic compiles at the cell's shapes are in
tests/test_pallas_topology_compile.py, the engine under both `impl`s in
tests/test_jamba.py."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, mamba, qwen2
from oryx_tpu.ops import paged_kv

F32 = jnp.float32
TOL = 5e-6  # float32 on both sides: summation order only
LAYERS, LI = 3, 1
LIVE = {
    "all": lambda B: np.ones(B, bool),
    "none": lambda B: np.zeros(B, bool),
    "mixed": lambda B: np.arange(B) % 3 != 1,
}
# expand 2 -> d 128, ONE tile of 128; expand 6 -> d 384, THREE of 128.
CASES = [(B, live, expand) for B in (1, 5, 64) for live in sorted(LIVE)
         for expand in (2, 6)]


def _llm(expand):
    return dataclasses.replace(cfg_lib.jamba_tiny().llm, mamba_expand=expand)


@functools.lru_cache(maxsize=None)
def _case(B, live, expand):
    """One step of layer LI over B lanes, by the kernels on the planes
    whole and by `mixer_step` on layer LI's rows sliced out (as
    `_hybrid_layers` runs the one and the other), from seeded planes
    with every layer's rows non-zero. Returns (cfg, the planes before,
    live, {"pallas" | "xla": (out [B, H], the planes after)})."""
    cfg = _llm(expand)
    d, N, K = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    assert mamba.step_fits(cfg, B) and not cfg.mamba_proj_bias
    keys = iter(jax.random.split(jax.random.key(B * 7 + expand), 8))
    mp = mamba.init_mixer_params(cfg, next(keys), LAYERS, F32)
    # 0.02-normal kernels leave dt's norm and the gate nearly idle.
    mp = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.key(a.size), a.shape),
        mp)
    lp = jax.tree_util.tree_map(lambda a: a[LI], mp)
    u = jax.random.normal(next(keys), (B, 1, cfg.hidden_size), F32)
    conv = jax.random.normal(next(keys), (LAYERS, B, (K - 1) * d), F32)
    ssm = jax.random.normal(next(keys), (LAYERS, B, N, d), F32)
    live = jnp.asarray(LIVE[live](B))
    got, planes = mamba.mixer_step_inplace(
        cfg, lp, mamba.step_invariants(mp, live, F32), jnp.int32(LI), u,
        (conv, ssm))
    want, (conv1, h1) = mamba.mixer_step(
        cfg, lp, u, (conv[LI].reshape(B, K - 1, d), ssm[LI]), live)
    out = {
        "pallas": (got[:, 0], *planes),
        "xla": (want[:, 0], conv.at[LI].set(conv1.reshape(B, -1)),
                ssm.at[LI].set(h1)),
    }
    return cfg, (np.asarray(conv), np.asarray(ssm)), np.asarray(live), {
        k: tuple(np.asarray(a) for a in v) for k, v in out.items()}


def _close(got, want):
    np.testing.assert_allclose(
        got, want, rtol=TOL, atol=TOL * float(np.abs(want).max()))


@pytest.mark.parametrize("B,live,expand", CASES)
def test_the_kernels_equal_the_mixer_step_they_replace(B, live, expand):
    """The conv rows and the state of every lane, the mixer's output of
    the live ones (the kernel skips a dead lane's state: its y is
    zeros, and so is `out_proj` of it, where `mixer_step` computes one
    that nobody reads)."""
    *_, alive, out = _case(B, live, expand)
    (y, *planes), (y_, *planes_) = out["pallas"], out["xla"]
    for got, want in zip(planes, planes_):
        _close(got, want)
    if alive.any():
        _close(y[alive], y_[alive])
    assert not y[~alive].any()


@pytest.mark.parametrize("B,live,expand", CASES)
def test_a_dead_lane_and_every_other_layer_keep_their_bits(B, live, expand):
    """Layer LI's rows of the live lanes are the only elements of the
    two planes that change; a live lane's do change."""
    _, planes, alive, out = _case(B, live, expand)
    for before, after in zip(planes, out["pallas"][1:]):
        other = [l for l in range(LAYERS) if l != LI]
        assert np.array_equal(before[other], after[other])
        assert np.array_equal(before[LI][~alive], after[LI][~alive])
        for b in np.flatnonzero(alive):
            assert (before[LI][b] != after[LI][b]).any()


@pytest.mark.parametrize("B,expand", [(5, 2), (5, 6), (64, 2), (64, 6)])
def test_the_state_stays_float32_by_the_comparisons_rule(B, expand):
    """`correctness_jamba`'s `state` clause on the rows the kernel
    wrote: the share of non-zero float32 elements that a bfloat16 holds
    exactly (low 16 mantissa bits zero) stays under its limit."""
    from benchmark import correctness_jamba

    *_, alive, out = _case(B, "mixed", expand)
    bits = out["pallas"][2][LI][alive].view(np.uint32)
    share = float(((bits & 0xFFFF) == 0)[bits != 0].mean())
    assert share <= correctness_jamba.STATE_BF16_MAX


@pytest.mark.parametrize("why,S,change", [
    ("channels not in whole lane tiles", 4, dict(mamba_expand=1)),
    ("a pool of slots no block of 16 divides", 24, {}),
    ("the state's N not in whole sublane tiles", 4, dict(mamba_d_state=4)),
])
def test_a_shape_the_tiles_do_not_fit_keeps_the_mixer_step(
        why, S, change, monkeypatch):
    """As `selective_scan`: under "pallas" the decode chunk of such a
    shape never reaches the kernels and advances the state as under
    "xla" (the attention kernel's summation order apart)."""
    cfg = dataclasses.replace(cfg_lib.jamba_tiny().llm, **change)
    assert not mamba.step_fits(cfg, S), why
    monkeypatch.setattr(mamba, "mixer_step_inplace", None)
    params = qwen2.init_params(cfg, jax.random.key(0))
    bt = jnp.arange(S, dtype=jnp.int32)[:, None]
    live = jnp.arange(S) % 3 != 1
    planes = {}
    for impl in ("xla", "pallas"):
        kv = qwen2.init_paged_kv_cache(cfg, S, 16, dtype=F32, num_slots=S)
        kv = {n: jax.random.normal(jax.random.key(len(n)), a.shape, a.dtype)
              for n, a in kv.items()}
        kv, *_ = generate.paged_decode_chunk(
            params, cfg, kv, bt, jnp.full((S,), 5, jnp.int32),
            jnp.full((S,), 3, jnp.int32), ~live,
            jnp.zeros((S, 0), jnp.int32), jax.random.split(
                jax.random.key(1), S),
            jnp.zeros((S,)), jnp.ones((S,)), jnp.zeros((S,), jnp.int32),
            chunk=2, eos=cfg.vocab_size, attn_impl=impl)
        planes[impl] = [np.asarray(kv[n]) for n in paged_kv.SLOT_PLANES]
    for got, want in zip(planes["pallas"], planes["xla"]):
        _close(got, want)
