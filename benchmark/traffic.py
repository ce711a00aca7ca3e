"""One general traffic generator, driven by a workload file.

Everything a cell sends is a pure function of (workload parameters,
seed): the arrival schedule, every prompt, every `max_tokens`, every
image and video. Later PRs add cells by adding parameter files; this
module has no per-cell code.

Steadiness rule (the contract's): every seed gets the SAME multiset of
lengths and gaps — quantiles of the stated distribution, one per slot —
in ONE fixed order (the mix's `order_seed` shuffle). An open-loop
schedule is the same for every seed, request for request and gap for
gap: the seed makes every word sent and the weights, and nothing else.
(A rotation of the schedule puts other turns of a session ahead of
their history and other prompts between a request's decode chunks:
`tpot_p90_ms` read 2-3 % apart between rotations that each repeated
themselves; PERF.md section 6, PR 23.) A closed loop's seed still
rotates each client's list. Which request follows which, and after
what gap, decides where queues build, and a p90 over the ~60 requests a window holds swings by
tens of percent between two free shuffles. The spreads the bounds are
set from therefore say how the system varies under one order of
traffic, not how traffic of the kind varies.
"""

from __future__ import annotations

import base64
import io
import math
import random
import statistics

WORDS = (
    "oryx", "frame", "video", "token", "patch", "scene", "what", "where",
    "describe", "count", "colour", "before", "after", "object", "person",
)


def text_of(rng: random.Random, chars: int) -> str:
    """`chars` characters (= tokens, one id per character) of words."""
    out, n = [], -1  # the joined length: words plus single spaces
    while n < chars:
        w = rng.choice(WORDS)
        out.append(w)
        n += len(w) + 1
    return " ".join(out)[:chars]


def quantile_values(dist: dict, n: int) -> list[int]:
    """n values at the mid-quantiles of `dist`, deterministic:
    {"kind": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
    or {"kind": "uniform", "min": a, "max": b} or {"kind": "const",
    "value": v}."""
    if dist["kind"] == "const":
        return [int(dist["value"])] * n
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["kind"] == "uniform":
        lo, hi = dist["min"], dist["max"]
        return [int(round(lo + q * (hi - lo))) for q in qs]
    if dist["kind"] == "lognormal":
        nd = statistics.NormalDist(math.log(dist["median"]), dist["sigma"])
        return [
            int(min(dist["max"], max(dist["min"], round(math.exp(
                nd.inv_cdf(q)
            )))))
            for q in qs
        ]
    raise ValueError(f"unknown distribution kind {dist['kind']!r}")


def shuffled(values: list, rng: random.Random) -> list:
    values = list(values)
    rng.shuffle(values)
    return values


def arrival_offsets(arr: dict, n: int, rng: random.Random) -> list[float]:
    """n open-loop due instants (seconds from the window's start) of
    a Poisson process: the gaps are the mid-quantiles of Exp(rate) in
    `rng`'s order, so every schedule offers exactly the same load over
    the same span."""
    rate = arr["rate"]
    if arr.get("process", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    # Mid-quantile gaps under-weigh the tail: rescale to the mean.
    scale = (n / rate) / sum(gaps)
    gaps = shuffled([g * scale for g in gaps], rng)
    out, t = [], 0.0
    for g in gaps:
        t += g
        out.append(t)
    return out


# --------------------------------------------------------------------------
# media
# --------------------------------------------------------------------------


def rotated(values: list, k: int) -> list:
    k %= max(1, len(values))
    return list(values[k:]) + list(values[:k])


def _png_data_uri(arr) -> str:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="PNG", compress_level=1)
    return "data:image/png;base64," + base64.b64encode(buf.getvalue()).decode()


def block_image(np_rng, side: int, block: int):
    """uint8 [side, side, 3]: a coarse random colour grid upsampled —
    seed-made pixels that a PNG holds in a few KB (a photo compresses;
    noise does not, and 64 noise frames would be 13 MB a request)."""
    import numpy as np

    cells = -(-side // block)
    grid = np_rng.integers(0, 256, (cells, cells, 3), dtype=np.uint8)
    img = np.repeat(np.repeat(grid, block, axis=0), block, axis=1)
    return np.ascontiguousarray(img[:side, :side])


def make_medium(spec: dict, side: int, seed: int) -> list[str]:
    """Content parts for one medium: one image, or `frames` frames."""
    import numpy as np

    np_rng = np.random.default_rng(seed)
    n = spec.get("frames", 1)
    return [
        _png_data_uri(block_image(np_rng, side, spec.get("block", 28)))
        for _ in range(n)
    ]


# --------------------------------------------------------------------------
# sessions -> requests
# --------------------------------------------------------------------------


def chat_body(messages, max_tokens: int, video: bool = False) -> dict:
    body = {
        "messages": messages, "max_tokens": int(max_tokens),
        "temperature": 0.0, "stream": True,
        "stream_options": {"include_usage": True},
    }
    if video:
        body["video"] = True
    return body


def build_sessions(params: dict, seed: int, n_requests: int) -> list[list]:
    """Sessions, each a list of request bodies to be sent in order (a
    later turn re-sends the history, so the prefix cache can work).
    Returns at least `n_requests` requests in all.

    Text sessions: `system_tokens` of shared system prompt, `turns`
    user turns each; the assistant turns in the re-sent history are
    seed-made text of the asked length (the load generator does not
    wait for the model's own reply text: random weights give `<id>`
    strings several characters a token, which would change the work).
    Media sessions: one medium, `questions` requests about it."""
    rng = random.Random(seed)  # words
    order = random.Random(params.get("order_seed", 0))  # the one shuffle
    system = text_of(random.Random(7), params.get("system_tokens", 0))
    user_len = shuffled(
        quantile_values(params["user_tokens"], n_requests), order)
    out_len = shuffled(
        quantile_values(params["max_tokens"], n_requests), order)
    turns_cycle = params.get("turns", [1])
    media = params.get("media") or []
    side_q = {}
    sessions, made, s_idx = [], 0, 0
    while made < n_requests:
        n_turns = turns_cycle[s_idx % len(turns_cycle)]
        parts = None
        if media:
            spec = media[s_idx % len(media)]
            key = s_idx % len(media)
            if key not in side_q:
                side_q[key] = shuffled(quantile_values(
                    spec["side"], max(1, n_requests // max(1, len(media)))
                ), order)
            sides = side_q[key]
            side = sides[(s_idx // len(media)) % len(sides)]
            side -= side % spec.get("multiple", 14)
            parts = make_medium(spec, side, seed * 1000003 + s_idx)
            n_turns = spec.get("questions", n_turns)
        session, history = [], []
        for t in range(n_turns):
            if made >= n_requests:
                break
            q = text_of(rng, user_len[made])
            if parts is not None:
                # Each question is a fresh single-turn request over the
                # same medium (media bind to the first user turn).
                content = [
                    {"type": "image_url", "image_url": {"url": u}}
                    for u in parts
                ] + [{"type": "text", "text": q}]
                msgs = [{"role": "user", "content": content}]
                video = bool(media[s_idx % len(media)].get("video"))
            else:
                msgs = ([{"role": "system", "content": system}] if system
                        else []) + history + [{"role": "user", "content": q}]
                video = False
                total = sum(len(m["content"]) for m in msgs) + out_len[made]
                if total > params.get("max_session_tokens", 1 << 30):
                    break  # the session ends before it outgrows max_ctx
            session.append(chat_body(msgs, out_len[made], video))
            if parts is None:
                history = history + [
                    {"role": "user", "content": q},
                    {"role": "assistant",
                     "content": text_of(rng, out_len[made])},
                ]
            made += 1
        if session:
            sessions.append(session)
        s_idx += 1
    return sessions


def warmup_bodies(params: dict, buckets, seed: int) -> list[dict]:
    """Requests that touch every shape the cell's traffic can reach:
    one text prompt inside each embed bucket up to the longest prompt,
    the first one twice (prefix-cache splice), and one of each medium
    at its largest side. (The copy-on-write path needs a prompt of an
    exact token count: runners/serve.warm_copy_on_write.)"""
    rng = random.Random(seed ^ 0x5EED)
    out = []
    hi = params.get("max_session_tokens") or (
        params.get("system_tokens", 0) + params["user_tokens"].get(
            "max", params["user_tokens"].get("value", 64))
    )
    media = params.get("media") or []
    if not media:
        prev = 0
        for b in buckets:
            # The middle of the bucket (the template adds its own
            # tokens on top), or of what the traffic reaches of it.
            n = (prev + min(b, hi)) // 2
            out.append(chat_body(
                [{"role": "user", "content": text_of(rng, max(8, n))}], 8
            ))
            prev = b
            if b >= hi:
                break
        out.append(out[0])
    for i, spec in enumerate(media):
        side = spec["side"].get("max", spec["side"].get("value"))
        side -= side % spec.get("multiple", 14)
        parts = make_medium(spec, side, seed + i)
        content = [
            {"type": "image_url", "image_url": {"url": u}} for u in parts
        ] + [{"type": "text", "text": text_of(rng, 32)}]
        out.append(chat_body(
            [{"role": "user", "content": content}], 8, bool(spec.get("video"))
        ))
    return out
