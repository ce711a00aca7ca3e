"""The device's time by the program's own layer names
(utils/profiling.DEVICE_SCOPES): the capture reader keeps an op's scope
path (utils/xplane.scope_seconds, over synthetic planes and wire
buffers), and every step program enters a vocabulary scope wherever it
makes real device work (the jaxprs of each model family's tiny preset,
over abstract parameters: nothing compiles, nothing runs)."""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import generate, qwen2
from oryx_tpu.utils import xplane
from oryx_tpu.utils.profiling import DEVICE_SCOPES, DEVICE_SUBSCOPES
from oryx_tpu.utils.xplane import Event, Line, Plane
from test_xplane import _event_with_offset, _field, _line, _plane

ROOT = Path(__file__).resolve().parent.parent

# ---------------------------------------------------------------------------
# (i) the reader
# ---------------------------------------------------------------------------

US = 1_000_000  # picoseconds


def _ops(events, modules=(), chip=0):
    """events: (offset_us, dur_us, name, op_name); modules: (offset_us,
    dur_us, name)."""
    return Plane(f"/device:TPU:{chip}", [
        Line("XLA Modules",
             [Event(n, d * US, o * US) for o, d, n in modules]),
        Line("XLA Ops",
             [Event(n, d * US, o * US, path) for o, d, n, path in events]),
    ])


DECODE = "jit(paged_decode_chunk)/jit(main)/"


def seconds(table):
    return {prog: {k: round(v[0] * 1e6, 3) for k, v in paths.items()}
            for prog, paths in table.items()}


def test_self_time_under_a_nested_while_and_the_deepest_subscope():
    # A while of 100 us holds an inner while of 60 us that holds a 50 us
    # kernel: the loops' own time is what their bodies leave.
    plane = _ops([
        (0, 100, "while.1", DECODE + "while"),
        (10, 60, "while.2", DECODE + "while/body/attn/mla/while"),
        (15, 50, "_dsa_attend.3",
         DECODE + "while/body/attn/mla/while/body/dsa_attend/pallas_call"),
        (75, 20, "fusion.7", DECODE + "while/body/moe/moe_routed/mul"),
        (100, 5, "fusion.9", DECODE + "while/body/head/dot_general"),
    ])
    table = xplane.scope_seconds([plane], DEVICE_SCOPES, DEVICE_SUBSCOPES)
    assert seconds(table) == {"paged_decode_chunk": {
        "unscoped": 20.0, "attn/mla": 10.0, "attn/dsa_attend": 50.0,
        "moe/moe_routed": 20.0, "head": 5.0}}
    assert sum(v[0] for v in table["paged_decode_chunk"].values()) \
        == pytest.approx(105e-6)
    # The model's own cut of a layer out of the stacked weights is
    # `stack`, a state row's cut inside `mixer` is the mixer's, and what
    # a scan does with its xs carries no scope.
    body = DECODE + "while/body/closed_call/while/body/"
    assert [xplane.scope_of(body + tail, DEVICE_SCOPES, DEVICE_SUBSCOPES)
            for tail in ("stack/squeeze:", "mixer/stack/dynamic_slice:",
                         "dynamic_slice:")] == ["stack", "mixer", "unscoped"]


def test_a_backward_op_falls_under_its_forwards_layer():
    train = "jit(train_step)/jit(main)/forward_backward/"
    plane = _ops([
        (0, 10, "fusion.1", train + "jvp(attn)/attn_global/dot_general"),
        (10, 30, "fusion.2",
         train + "transpose(jvp(attn))/attn_global/dot_general"),
        (40, 5, "fusion.3", train + "checkpoint(vmap(ffn))/mul"),
        (45, 5, "fusion.4", train + "transpose(jvp(loss))/while/body/dot"),
        (50, 7, "convert.5", train + "convert_element_type"),
    ])
    got = seconds(xplane.scope_seconds(
        [plane], DEVICE_SCOPES, DEVICE_SUBSCOPES))
    assert got == {"train_step": {
        "attn/attn_global": 40.0, "ffn": 5.0, "loss": 5.0,
        "unscoped": 7.0}}


def test_two_chips_are_averaged_and_an_op_without_a_path_is_unscoped():
    def chip(i, dur):
        return _ops(
            [(0, dur, "fusion.1", DECODE + "attn/dot_general"),
             (dur, 4, "copy.2", ""),       # inside the module: its program
             (500, 6, "copy.3", "")],      # outside every module
            modules=[(0, dur + 4, "jit_paged_decode_chunk(123456)")],
            chip=i)
    host = Plane("/host:CPU", [Line("python", [Event("x", 9 * US)])])
    got = seconds(xplane.scope_seconds(
        [chip(0, 10), chip(1, 30), host], DEVICE_SCOPES, DEVICE_SUBSCOPES))
    assert got == {"paged_decode_chunk": {"attn": 20.0, "unscoped": 4.0},
                   "": {"unscoped": 6.0}}
    assert xplane.scope_seconds([host], DEVICE_SCOPES) == {}


def _map_entry(key, *fields):
    """One map<int64, X{Event,Stat}Metadata> entry of a plane."""
    return _field(1, 0, key) + _field(
        2, 2, _field(1, 0, key) + b"".join(fields))


@pytest.mark.parametrize("stat_metadata", ["follow", "precede"])
def test_the_wire_reader_keeps_the_op_name_of_an_event_metadata(
        tmp_path, stat_metadata):
    """XEventMetadata.stats (field 5): the `tf_op` stat's `str_value`
    (5) is the op_name, wherever the plane's stat metadata lie in the
    buffer; a stat under another name, or with another kind of value,
    is none."""
    kernel = DECODE + "while/body/mixer/mamba/ssm_step/pallas_call:"
    fused = DECODE + "while/body/ffn/dot_general:"
    name = lambda n: _field(2, 2, n.encode())  # noqa: E731
    stat = lambda mid, *value: _field(  # noqa: E731
        5, 2, _field(1, 0, mid) + _field(*value))
    stat_names = b"".join(_field(5, 2, e) for e in (
        _map_entry(21, name(xplane.OP_NAME_STAT)),
        _map_entry(22, name("flops")), _map_entry(23, name("source"))))
    plane = _plane(
        "/device:TPU:0",
        [_line("XLA Ops", [_event_with_offset(7, 8 * US, 0),
                           _event_with_offset(8, 2 * US, 8 * US),
                           _event_with_offset(9, 1 * US, 10 * US)])],
        [_map_entry(7, name("_ssm_step.4"), stat(21, 5, 2, kernel.encode())),
         _map_entry(8, name("fusion.5"), stat(22, 3, 0, 12345),
                    stat(21, 5, 2, fused.encode())),
         _map_entry(9, name("copy.6"), stat(23, 5, 2, b"qwen2.py:1"),
                    stat(21, 3, 0, 4))],
    )
    if stat_metadata == "follow":
        plane = plane + stat_names
    else:  # fields of one message may come in any order
        plane = stat_names + plane
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, 2, plane))
    planes = xplane.parse_xspace(str(path))
    assert [(e.name, e.op_name) for e in planes[0].lines[0].events] == [
        ("_ssm_step.4", kernel), ("fusion.5", fused), ("copy.6", "")]
    got = seconds(xplane.scope_seconds(
        planes, DEVICE_SCOPES, DEVICE_SUBSCOPES))
    assert got == {"paged_decode_chunk": {
        "mixer/ssm_step": 8.0, "ffn": 2.0}, "": {"unscoped": 1.0}}


def test_every_scope_the_program_enters_is_in_the_vocabulary():
    """One vocabulary: a `jax.named_scope` literal under oryx_tpu/ is a
    top-level layer, a scope inside one, or the train step's two
    phases (which lie above the layers and are no layer's)."""
    known = set(DEVICE_SCOPES) | set(DEVICE_SUBSCOPES) | {
        "forward_backward", "forward_backward_accum"}
    assert not set(DEVICE_SCOPES) & set(DEVICE_SUBSCOPES)
    entered = set()
    for path in (ROOT / "oryx_tpu").rglob("*.py"):
        for args in re.findall(
                r"named_scope\(([^()]*(?:\([^()]*\))?[^()]*)\)",
                path.read_text()):
            entered |= set(re.findall(r'"(\w+)"', args))
    assert entered <= known, sorted(entered - known)
    assert set(DEVICE_SCOPES) <= entered, sorted(
        set(DEVICE_SCOPES) - entered)


# ---------------------------------------------------------------------------
# (ii) the serve programs and the plain forward of every family
# ---------------------------------------------------------------------------

# What is real device work: a product, a kernel, a gather or a scatter
# of rows, a convolution.
HEAVY = ("dot_general", "ragged_dot", "pallas_call", "gather", "scatter",
         "scatter-add", "scatter_add", "conv_general_dilated")


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else (v,)):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def equations(jaxpr, above=""):
    """(primitive, name stack) of every equation, sub-jaxprs included.
    An inner jaxpr's name stacks are relative to the equation that
    holds it."""
    for eqn in jaxpr.eqns:
        stack = above + "/" + str(eqn.source_info.name_stack)
        yield eqn.primitive.name, stack
        for sub in _sub_jaxprs(eqn):
            yield from equations(sub, stack)


def unscoped_work(jaxpr):
    """The HEAVY equations that lie under no DEVICE_SCOPES name."""
    return [(prim, stack) for prim, stack in equations(jaxpr)
            if prim in HEAVY
            and xplane.scope_of(stack, DEVICE_SCOPES) == xplane.UNSCOPED]


def scopes_entered(jaxpr):
    return {xplane.scope_of(stack, DEVICE_SCOPES)
            for _, stack in equations(jaxpr)}


# preset -> the top-level scopes its serve programs must enter (`stack`
# where the model's own code, not a scan, cuts a layer out of the
# stacked weights)
FAMILIES = {
    "tiny_llm": {"attn", "ffn"},
    "sdar_tiny": {"attn", "moe"},
    "longcat_tiny": {"attn", "ffn", "moe"},
    "mistral4_tiny": {"attn", "moe"},
    "jamba_tiny": {"attn", "ffn", "mixer", "stack"},
    "smallthinker_tiny": {"attn", "moe", "stack"},
    "glm5_tiny": {"attn", "ffn", "moe"},
    "lfm2_tiny": {"attn", "ffn", "moe", "mixer", "stack"},
    "nemotron3_tiny": {"attn", "moe", "mixer", "stack"},
}
S, PAGE, PAGES, TABLE = 2, 8, 16, 4


def _llm(name):
    c = getattr(cfg_lib, name)()
    return c if name == "tiny_llm" else c.llm


def _i32(*s):
    return jax.ShapeDtypeStruct(s, jnp.int32)


def _f32(*s):
    return jax.ShapeDtypeStruct(s, jnp.float32)


def _program_jaxpr(cfg, program):
    p = jax.eval_shape(lambda: qwen2.init_params(cfg, jax.random.key(0)))
    if program == "forward":
        return jax.make_jaxpr(lambda p, ids: qwen2.forward(
            p, cfg, input_ids=ids, remat=True))(p, _i32(S, 2 * PAGE))
    kv = jax.eval_shape(lambda: qwen2.init_paged_kv_cache(
        cfg, (PAGES, PAGES) if cfg.windowed else PAGES, PAGE, jnp.float32,
        **({"num_slots": S} if cfg.recurrent else {})))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), S))
    sampling = (keys, _f32(S), _f32(S), _i32(S))
    window = ({"window_tables": _i32(S, TABLE), "window_base": _i32(S)}
              if cfg.windowed else {})
    if program == "paged_prefill":
        more = dict(window, **({"slots": _i32(S)} if cfg.recurrent else {}))
        return jax.make_jaxpr(
            lambda p, e, n, bt, kv, st, k, t, tp, tk, more:
            generate.paged_prefill(
                p, cfg, e, n, bt, kv, st, k, t, tp, tk, **more))(
            p, _f32(S, PAGE, cfg.hidden_size), _i32(S), _i32(S, TABLE), kv,
            _i32(S), *sampling, more)
    if cfg.block_length:
        B = cfg.block_length
        return jax.make_jaxpr(
            lambda p, kv, bt, blk, nk, n, fin, k, t, tp, tk, pend, live:
            generate.paged_block_step(
                p, cfg, kv, bt, blk, nk, n, fin, k, t, tp, tk, pend, live,
                steps=2, remasking="low_confidence_dynamic", threshold=0.9,
                eos=1))(
            p, kv, _i32(S, TABLE), _i32(S, B), _i32(S), _i32(S),
            jax.ShapeDtypeStruct((S,), jnp.bool_), *sampling, _i32(S, B),
            jax.ShapeDtypeStruct((S,), jnp.bool_))
    return jax.make_jaxpr(
        lambda p, kv, bt, tok, n, fin, rec, k, t, tp, tk, window:
        generate.paged_decode_chunk(
            p, cfg, kv, bt, tok, n, fin, rec, k, t, tp, tk, chunk=2, eos=1,
            **window))(
        p, kv, _i32(S, TABLE), _i32(S), _i32(S),
        jax.ShapeDtypeStruct((S,), jnp.bool_), _i32(S, 0), *sampling, window)


@pytest.mark.parametrize("program", ["forward", "paged_prefill", "step"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_real_work_lies_under_a_vocabulary_scope(family, program):
    """No product, kernel, gather, scatter or convolution of a step
    program is traced outside the vocabulary, and the program enters
    the layers its family has, the embedding, the head and (where it
    samples) the sampler."""
    cfg = _llm(family)
    if program == "forward" and cfg.block_length:
        pytest.skip("a block-diffusion model trains nowhere in this repo")
    jaxpr = _program_jaxpr(cfg, program).jaxpr
    assert unscoped_work(jaxpr) == []
    need = FAMILIES[family] | {"head"}
    need |= {"embed"} if program != "paged_prefill" else set()  # (embeds in)
    need |= {"sample"} if program != "forward" else set()
    assert need <= scopes_entered(jaxpr), need - scopes_entered(jaxpr)


# ---------------------------------------------------------------------------
# (iii) the train step
# ---------------------------------------------------------------------------


def test_the_train_step_enters_loss_and_optimizer_update():
    import numpy as np

    from oryx_tpu.constants import IGNORE_INDEX, IMAGE_TOKEN_INDEX
    from oryx_tpu.models import oryx, splice
    from oryx_tpu.ops import packing
    from oryx_tpu.train import optimizer, step as step_lib

    cfg = cfg_lib.oryx_tiny()
    p = cfg.vision.patch_size
    packed = packing.pack_images(
        [np.zeros((2 * p, 2 * p, 3), np.float32)] * 2, patch_size=p,
        base_grid=cfg.vision.base_grid, side_factors=1, buckets=(64,))
    row = np.array([5, IMAGE_TOKEN_INDEX, 7, 8, 9])
    labels = np.where(row > 6, row, IGNORE_INDEX)
    mm = splice.build_mm_batch(
        [row] * 2, splice.query_slots(packed), labels=[labels] * 2,
        buckets=(16,))
    batch = {
        "patches": packed.patches, "segment_ids": packed.segment_ids,
        "pos_coords": packed.pos_coords, "region_ids": packed.region_ids,
        "q_region_ids": packed.q_region_ids, "token_ids": mm.token_ids,
        "visual_idx": mm.visual_idx, "is_visual": mm.is_visual,
        "attn_mask": mm.attn_mask, "positions": mm.positions,
        "labels": mm.labels,
    }
    batch = jax.tree.map(  # [accum = 1, ...], abstract
        lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype), batch)
    params = jax.eval_shape(
        lambda: oryx.init_params(cfg, jax.random.key(0)))
    tx = optimizer.make_optimizer(cfg.train, params)
    state = jax.eval_shape(
        lambda: step_lib.init_state(cfg, tx, jax.random.key(0)))
    jaxpr = jax.make_jaxpr(
        lambda s, b: step_lib.train_step_fn(s, b, cfg, tx))(state, batch)
    assert unscoped_work(jaxpr.jaxpr) == []
    need = {"vision", "embed", "attn", "ffn", "head", "loss",
            "optimizer_update"}
    assert need <= scopes_entered(jaxpr.jaxpr), \
        need - scopes_entered(jaxpr.jaxpr)
