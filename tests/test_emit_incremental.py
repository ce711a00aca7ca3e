"""The emit phase pays for a chunk's tokens, not for the reply so far.

`ContinuousScheduler._advance` (through `pipeline.ReplyText`) decodes a
chunk behind a few tokens of context, scans for a stop where one could
have completed, and samples page-seconds with one read of the
allocator's refcounts. Each is held here, chunk by chunk, to the plain
rules it replaced: the whole reply decoded again at every chunk
(`RefEmit`, the rules as they stood before, kept in this file), and the
entry-by-entry walk of the block table (`walk_page_seconds`)."""

import time

import numpy as np
import pytest

import jax

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve import scheduler as sched_lib
from oryx_tpu.serve.pipeline import OryxInference
from oryx_tpu.serve.scheduler import (
    ContinuousScheduler, RequestHandle, _Request,
)
from oryx_tpu.utils.metrics import ServingMetrics

# ---------------------------------------------------------------------------
# Tokenizers
# ---------------------------------------------------------------------------


class IdTokenizer:
    """The benchmark's stand-in: `<id>` per token out."""

    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


class CharTokenizer(IdTokenizer):
    """A character a token (a token past the table decodes to
    nothing, as a skipped special token does)."""

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


class ByteTokenizer(IdTokenizer):
    """Byte level: token i is the byte i - 3, so a character of 2-4
    bytes is as many tokens, and one that a chunk cuts decodes to
    U+FFFD until its last byte is there."""

    def encode(self, text, add_special_tokens=False):
        return [b + 3 for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return bytes(int(i) - 3 for i in ids if 3 <= i < 259).decode(
            "utf-8", errors="replace")


class PieceTokenizer(IdTokenizer):
    """Sentencepiece-like: a token is a piece, a word's first piece
    carries its leading space as U+2581, and decode drops the space
    in front of the FIRST piece it is handed: what a token decodes to
    depends on whether one stands before it."""

    PIECES = ["▁the", "▁qui", "ck", "▁brown", "▁fox",
              "es", ",", "▁jump", "ed", "▁over", ".", "\n",
              "▁", "▁STOP", "!"]

    def decode(self, ids, skip_special_tokens=True):
        text = "".join(
            self.PIECES[(int(i) - 3) % len(self.PIECES)] for i in ids
        ).replace("▁", " ")
        return text[1:] if text.startswith(" ") else text


EOS = cfg_lib.oryx_tiny().generation.eos_token_id

# ---------------------------------------------------------------------------
# The reference: the rules before this change, the reply decoded whole
# ---------------------------------------------------------------------------


def ref_stop_cut(text, stops):
    cut = min((i for s in stops if (i := text.find(s)) >= 0), default=-1)
    return (text[:cut], True) if cut >= 0 else (text, False)


def ref_stop_token_count(tokenizer, emitted, stops, chunk_start):
    for k in range(chunk_start + 1, len(emitted) + 1):
        if ref_stop_cut(tokenizer.decode(list(emitted[:k])), stops)[1]:
            return k
    return len(emitted)


def ref_stable_text_prefix(text, stops):
    text = text.lstrip()
    while text.endswith("�"):
        text = text[:-1]
    held = 0
    for s in stops:
        for i in range(len(s) - 1, 0, -1):
            if text.endswith(s[:i]):
                held = max(held, i)
                break
    if held:
        text = text[: len(text) - held]
    return text.rstrip()


class RefEmit:
    """`_advance` as it stood: every chunk decodes `emitted` whole."""

    def __init__(self, tokenizer, stops, max_new):
        self.tokenizer, self.stops, self.max_new = tokenizer, stops, max_new
        self.replay = 0
        self.emitted = []
        self.text_done = ""
        self.deltas = []
        self.finish = None  # (reason, completion tokens)
        self.cost_decode_tokens = 0

    def _emit(self, safe):
        if len(safe) > len(self.text_done):
            self.deltas.append(safe[len(self.text_done):])
            self.text_done = safe

    def advance(self, tokens):
        useful = 0
        chunk_start = len(self.emitted)
        finish = None
        for t in tokens:
            if self.replay > 0:
                self.replay -= 1
                continue
            useful += 1
            if t == EOS:
                finish = ("stop", len(self.emitted) + 1)
                break
            self.emitted.append(t)
            if len(self.emitted) >= self.max_new:
                finish = ("length", len(self.emitted))
                break
        if len(self.emitted) == chunk_start and finish is None:
            return useful
        text = self.tokenizer.decode(self.emitted)
        text, hit = ref_stop_cut(text, self.stops)
        if hit:
            n = ref_stop_token_count(
                self.tokenizer, self.emitted, self.stops, chunk_start)
            if finish is None or n <= finish[1]:
                finish = ("stop", n)
        if finish is not None:
            useful = min(useful, finish[1] - chunk_start)
        self.cost_decode_tokens += useful
        if finish is not None:
            self._emit(text.strip())
            self.finish = finish
        else:
            self._emit(ref_stable_text_prefix(text, self.stops))
        return useful


# ---------------------------------------------------------------------------
# Driving the scheduler's own `_advance`
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def params():
    return oryx.init_params(cfg_lib.oryx_tiny(), jax.random.key(0))


def _sched(params, tokenizer, **kw):
    pipe = OryxInference(tokenizer, params, cfg_lib.oryx_tiny())
    metrics = ServingMetrics()
    return ContinuousScheduler(
        pipe, num_slots=2, page_size=16, chunk=8, max_ctx=512,
        metrics=metrics, autostart=False, **kw,
    ), metrics


def _place(sched, stops, max_new, s=0):
    """A streaming request in slot s, as after its activation."""
    h = RequestHandle()
    h.streaming = True
    tr = sched.tracer.start_trace("request")
    h.trace, h.request_id = tr, tr.id
    req = _Request(
        request={}, max_new=max_new, sampling={}, handle=h,
        submit_time=time.monotonic(), stops=list(stops), trace=tr,
    )
    req.length = 4
    req.activated = True
    sched.slots[s] = req
    sched.lengths[s] = req.length
    return req, h


def _drain(h):
    deltas, end = [], None
    while not h.events.empty():
        ev = h.events.get_nowait()
        if ev[0] == "delta":
            deltas.append(ev[1])
        else:
            end = ev
    return deltas, end


def _chunks(tokens, sizes):
    """`tokens` cut into chunks whose sizes cycle through `sizes`."""
    out, i, k = [], 0, 0
    while i < len(tokens):
        n = sizes[k % len(sizes)]
        out.append(tokens[i:i + n])
        i, k = i + n, k + 1
    return out


_BYTES = ByteTokenizer()
_CHARS = CharTokenizer()
_PIECE = PieceTokenizer()
_WORDS = "naïve 中文 café \U0001f600 ok €5, done"

# name -> (tokenizer, device tokens, stops, max_new, tokens emitted
# before an eviction or 0)
STREAMS = {
    "id_standin": (
        IdTokenizer(), list(range(3, 99)), [], 200, 0),
    "id_standin_stop_inside_ids": (
        IdTokenizer(), list(range(3, 99)), ["<41><4"], 200, 0),
    "bytes_split_characters": (
        _BYTES, _BYTES.encode(_WORDS * 3), [], 500, 0),
    "bytes_stop_after_split_character": (
        _BYTES, _BYTES.encode(_WORDS + " €END " + _WORDS),
        ["€END"], 500, 0),
    "bytes_length_cap_inside_a_character": (
        _BYTES, _BYTES.encode("ab 中文中文中"), [], 10, 0),
    "bytes_garbage": (
        _BYTES, [3 + b for b in (0x80, 0x80, 0xE2, 0x41, 0xE2, 0x82, 0x80,
                                 0xC3, 0x28, 0xF0, 0x9F, 0x98, 0x42, 0x80,
                                 0xE4, 0xB8, 0xAD, 0xE4, 0xB8)] * 2,
        [], 500, 0),
    "pieces_leading_space": (
        _PIECE, [3 + i for i in (0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 0, 4, 10,
                                 11, 0, 1, 2, 4, 10)], [], 500, 0),
    "pieces_whitespace_first_and_last": (
        _PIECE, [3 + i for i in (12, 11, 12, 0, 4, 12, 12, 11, 11, 12, 7,
                                 8, 12, 11)], [], 500, 0),
    "pieces_stop_with_leading_space": (
        _PIECE, [3 + i for i in (0, 1, 2, 4, 6, 13, 14, 0, 4, 10)],
        [" STOP!", "\n\n"], 500, 0),
    "stop_straddles_a_chunk": (
        _CHARS, _CHARS.encode("abcdefgSTOPhijklmnop"),
        ["gSTOPh"], 100, 0),
    "stop_prefix_held_then_broken": (
        _CHARS, _CHARS.encode("abcSTOabcSTabcSTOPabc"),
        ["STOP", "xyz"], 100, 0),
    "stop_in_leading_whitespace": (
        _CHARS, _CHARS.encode("\n\nAB and more text"),
        ["\n\nAB"], 100, 0),
    "stop_starts_in_text_already_sent": (
        _CHARS, _CHARS.encode("xA yA B tail"),
        ["A B"], 100, 0),
    "stop_and_eos_in_one_chunk": (
        _CHARS,
        _CHARS.encode("abcdefghijkSTOPl") + [EOS] + [70] * 7,
        ["STOP"], 100, 0),
    "eos_before_stop_in_one_chunk": (
        _CHARS,
        _CHARS.encode("abcdefghij") + [EOS]
        + _CHARS.encode("STOPxx"), ["STOP"], 100, 0),
    "eos_alone": (
        _CHARS, _CHARS.encode("hello there  ") + [EOS, 70],
        [], 100, 0),
    "max_new_mid_chunk": (
        _CHARS, _CHARS.encode("abcdefghijklmnopqrstuvwx"),
        [], 13, 0),
    "max_new_and_stop_in_one_chunk": (
        _CHARS, _CHARS.encode("abcdefghiSTOPklmnopqrstu"),
        ["STOP"], 15, 0),
    "only_whitespace_then_length": (
        _CHARS, _CHARS.encode(" \n \n \n \n \n "), [], 9, 0),
    "skipped_tokens_decode_to_nothing": (
        _CHARS, [97, 600, 600, 98, 600, 99, 600, 600, 600, 600,
                          600, 600, 600, 600, 600, 600, 600, 100], ["cd"],
        100, 0),
    # An eviction after 12 tokens: the device replays them, the host
    # skips them, and the text goes on from where it was.
    "replay_skip": (
        _CHARS, _CHARS.encode("abcdefghijk lmnoSTOPq"),
        ["STOP"], 100, 12),
    "replay_skip_bytes_mid_character": (
        _BYTES, _BYTES.encode("ab中文cd中文 fin"), [], 100, 4),
}

SIZES = {"1": [1], "4": [4], "8": [8], "varying": [3, 1, 5, 2, 8, 1, 4]}


@pytest.mark.parametrize("sizes", list(SIZES))
@pytest.mark.parametrize("name", list(STREAMS))
def test_advance_equals_the_whole_reply_decode_at_every_chunk(
    params, name, sizes
):
    """Text, the deltas put on the handle, finish reason, usage,
    `cost_decode_tokens` and the useful-step count: the same as the
    whole-reply decode's at EVERY chunk, for every tokenizer, stop
    layout and chunk size (1 and a varying size are what speculation
    and block mode hand a lane)."""
    tok, tokens, stops, max_new, evict_after = STREAMS[name]
    sched, _ = _sched(params, tok)
    req, h = _place(sched, stops, max_new)
    ref = RefEmit(tok, stops, max_new)
    chunks = _chunks(tokens, SIZES[sizes])
    if evict_after:
        # Emit the first tokens, in chunks, then evict: `replay` covers
        # what was processed, and the device sends it all again.
        for c in _chunks(tokens[:evict_after], SIZES[sizes]):
            assert sched._advance(0, list(c)) == ref.advance(list(c))
        got, _ = _drain(h)
        assert got == ref.deltas
        ref.deltas = []
        req.replay = ref.replay = req.processed
        assert req.replay == evict_after
    try:
        for c in chunks:
            useful = sched._advance(0, list(c))
            assert useful == ref.advance(list(c)), c
            got, end = _drain(h)
            assert got == ref.deltas, (c, got, ref.deltas)
            ref.deltas = []
            assert req.text_done == ref.text_done
            assert req.emitted == ref.emitted
            assert req.cost_decode_tokens == ref.cost_decode_tokens
            if ref.finish is not None:
                assert h.done.is_set() and end[0] == "end"
                assert h.finish_reason == ref.finish[0]
                assert h.usage == (4, ref.finish[1])
                assert h.reply == ref.text_done
                assert sched.slots[0] is None
                break
            assert not h.done.is_set() and end is None
            # what the client has is the stable part of the whole decode
            assert req.text_done == ref_stable_text_prefix(
                ref_stop_cut(tok.decode(req.emitted), stops)[0], stops)
    finally:
        sched.slots = [None, None]
        sched.close()


# ---------------------------------------------------------------------------
# The bound: tokens handed to `decode`, no clock
# ---------------------------------------------------------------------------


class CountingTokenizer(IdTokenizer):
    def __init__(self):
        self.tokens = 0
        self.calls = 0

    def decode(self, ids, skip_special_tokens=True):
        self.tokens += len(ids)
        self.calls += 1
        return super().decode(ids)


@pytest.mark.parametrize("chunk", [1, 4, 8])
def test_decode_sees_a_constant_times_the_reply_not_its_square(
    params, chunk
):
    """A 2,048-token reply in chunks of 8: `decode` is handed a small
    constant x 2,048 tokens (the chunk twice behind 4 tokens of
    context: (4 + 12) / 8 = 2 a token, 9 a token at a chunk of 1),
    where decoding the reply whole at every chunk is handed
    2,048^2 / 16 = 262,000; `emit_decoded_tokens_total` reads the same
    count, beside `decode_steps_useful`'s 2,048."""
    n = 2048
    tok = CountingTokenizer()
    sched, metrics = _sched(params, tok)
    req, h = _place(sched, ["<7><7><7>"], n)
    ids = [3 + (i * 37) % 400 for i in range(n)]
    try:
        useful = sum(
            sched._advance(0, c) for c in _chunks(ids, [chunk]))
    finally:
        sched.slots = [None, None]
        sched.close()
    assert h.done.is_set() and h.finish_reason == "length"
    assert useful == n and h.usage == (4, n)
    assert h.reply == IdTokenizer().decode(ids)
    per_token = {1: 9, 4: 3, 8: 2}[chunk]
    assert n < tok.tokens <= per_token * n, tok.tokens
    assert tok.calls <= 2 * (n // chunk)
    assert metrics.get("emit_decoded_tokens_total") == tok.tokens
    whole = sum(range(chunk, n + 1, chunk))
    assert whole >= 30 * tok.tokens  # 262,656 at a chunk of 8


def test_stop_token_count_decodes_from_the_offset(params):
    """The chunk a stop completes in costs its own prefixes (each
    decoded from the context on), not the reply's: 1,000 tokens in,
    then a stop in the 5th token of a chunk of 8."""
    tok = CountingTokenizer()
    sched, metrics = _sched(params, tok)
    req, h = _place(sched, ["<9><9>"], 4096)
    ids = [3 + (i * 37) % 400 for i in range(1000)]
    try:
        for c in _chunks(ids, [8]):
            sched._advance(0, c)
        before = tok.tokens
        useful = sched._advance(0, [11, 12, 13, 9, 9, 14, 15, 16])
    finally:
        sched.slots = [None, None]
        sched.close()
    assert h.done.is_set() and h.finish_reason == "stop"
    assert h.usage == (4, 1005) and useful == 5
    assert h.reply.endswith("<11><12><13>") and len(req.emitted) == 1008
    # context + chunk twice, then prefixes of 1..5 tokens behind 4
    assert tok.tokens - before <= 4 + 12 + sum(4 + k for k in range(1, 6))


# ---------------------------------------------------------------------------
# Page-seconds: one read of the refcounts gives what the walk gave
# ---------------------------------------------------------------------------


def walk_page_seconds(sched, s, now):
    """`_accrue_page_seconds` as it stood: the table walked entry by
    entry, a `refcount()` call each."""
    req = sched.slots[s]
    if req is None or not req.pages_t:
        return
    held, weight = 0, 0.0
    for p in sched.bt[s]:
        if p != sched._sentinel:
            held += 1
            weight += 1.0 / max(1, sched.allocator.refcount(int(p)))
    req.ref_page_seconds += weight * (now - req.ref_pages_t)
    req.ref_pages_t = now
    if held > req.ref_peak_pages:
        req.ref_peak_pages = held
        req.ref_peak_page_seconds = req.ref_page_seconds


class _Clock:
    """time.monotonic for the scheduler module, stepped by the test."""

    def __init__(self):
        self.t = 100.0

    def monotonic(self):
        return self.t

    def __getattr__(self, name):
        return getattr(time, name)


def _both(sched, s, clock, dt):
    """Let `dt` pass, then sample slot s both ways."""
    clock.t += dt
    walk_page_seconds(sched, s, clock.t)
    sched._accrue_page_seconds(s)
    req = sched.slots[s]
    if req is not None:
        assert req.cost_page_seconds == pytest.approx(
            req.ref_page_seconds, rel=1e-12, abs=1e-15)
        assert req.peak_pages == req.ref_peak_pages
        assert req.peak_page_seconds == pytest.approx(
            req.ref_peak_page_seconds, rel=1e-12, abs=1e-15)



@pytest.mark.parametrize("plane", ["global", "window"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_page_seconds_without_the_walk(params, monkeypatch, plane, seed):
    """`cost_page_seconds`, `peak_pages` and `peak_page_seconds` over a
    seeded sequence of grow, splice of shared pages, release by a
    neighbour, eviction and free equal the entry-by-entry walk's, on
    the global plane alone and with a window plane present."""
    clock = _Clock()
    monkeypatch.setattr(sched_lib, "time", clock)
    if plane == "window":
        # tests/test_smallthinker_engine.py's engine: two paged planes
        import test_smallthinker_engine as win

        cfg = cfg_lib.smallthinker_tiny()
        sched = win._engine(
            OryxInference(win.IdTokenizer(),
                          oryx.init_params(cfg, jax.random.key(0)), cfg,
                          template="plain"),
            metrics=ServingMetrics(), num_slots=3)
        assert sched.wplane is not None
    else:
        sched = ContinuousScheduler(
            OryxInference(CharTokenizer(), params, cfg_lib.oryx_tiny()),
            num_slots=3, page_size=16, chunk=4, max_ctx=512,
            metrics=ServingMetrics(), autostart=False,
        )
    page = sched.allocator.page_size
    rng = np.random.default_rng(seed)
    alloc = sched.allocator
    reqs, placed = {}, []
    shared_seen = False  # a sample saw a page with two holders

    def place(s):
        req, _ = _place(sched, [], 100, s)
        req.pages_t = clock.t
        req.ref_pages_t = clock.t
        req.ref_page_seconds = 0.0
        req.ref_peak_pages = 0
        req.ref_peak_page_seconds = 0.0
        reqs[s] = req
        placed.append(req)

    def held(s):
        return [int(p) for p in sched.bt[s] if p != sched._sentinel]

    try:
        for s in range(3):
            place(s)
        tokens = {s: 0 for s in range(3)}
        for step in range(60):
            s = int(rng.integers(3))
            op = rng.choice(["grow", "splice", "release", "evict", "tick"],
                            p=[0.4, 0.25, 0.1, 0.1, 0.15])
            for t in range(3):
                _both(sched, t, clock, float(rng.uniform(0.0, 0.3)))
                shared_seen |= any(
                    alloc.refcount(p) > 1 for p in held(t))
            if sched.slots[s] is None:
                place(s)
                tokens[s] = 0
                continue
            if op == "grow":
                tokens[s] += int(rng.integers(1, 50))
                walk_page_seconds(sched, s, clock.t)
                assert sched._grow_slot(s, tokens[s])
            elif op == "splice":
                # Share a neighbour's leading pages into this slot's
                # free entries, as a prefix-cache splice does.
                donor = (s + 1) % 3
                pages = held(donor)[:int(rng.integers(1, 4))]
                n0 = len(held(s))
                if pages and sched.slots[donor] is not None \
                        and n0 + len(pages) <= sched.max_pages:
                    walk_page_seconds(sched, s, clock.t)
                    sched._accrue_page_seconds(s)
                    alloc.share(pages, owner=sched._owner_tag(reqs[s]))
                    sched.bt[s, n0:n0 + len(pages)] = pages
                    tokens[s] = (n0 + len(pages)) * page
            elif op == "release":
                # A neighbour lets go of everything it holds: pages it
                # shared with this slot weigh more from now on.
                t = (s + 2) % 3
                if sched.slots[t] is not None:
                    walk_page_seconds(sched, t, clock.t)
                    sched._clear_slot(t)
            elif op == "evict":
                walk_page_seconds(sched, s, clock.t)
                sched._accrue_page_seconds(s)
                sched._free_slot_pages(s)
                tokens[s] = 0
            alloc.check_invariant(
                [held(t) for t in range(3)])
        for s in range(3):
            _both(sched, s, clock, 0.25)
        assert max(r.peak_pages for r in placed) > 3
        assert sum(r.cost_page_seconds for r in placed) > 1.0
        assert shared_seen  # a page was weighed at less than 1
    finally:
        for s in range(3):
            if sched.slots[s] is not None:
                sched._free_slot_pages(s)
        sched.slots = [None] * 3
        sched.close()
