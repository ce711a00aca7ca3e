"""OpenAI-compatible API server tests: message parsing, a live server
round-trip through the continuous engine (JSON + streaming SSE),
concurrent clients sharing its slots."""

import base64
import io
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from oryx_tpu import config as cfg_lib
from oryx_tpu.models import oryx
from oryx_tpu.serve import api_server
from oryx_tpu.serve.pipeline import OryxInference


class FakeTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


def _data_uri(img: np.ndarray) -> str:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="PNG")
    return "data:image/png;base64," + base64.b64encode(buf.getvalue()).decode()


def test_parse_messages_history_and_images():
    img = np.random.default_rng(0).integers(
        0, 255, size=(16, 16, 3), dtype=np.uint8
    )
    messages = [
        {"role": "system", "content": "be brief"},
        {"role": "user", "content": [
            {"type": "text", "text": "what is this?"},
            {"type": "image_url", "image_url": {"url": _data_uri(img)}},
        ]},
        {"role": "assistant", "content": "a cat"},
        {"role": "user", "content": "why?"},
    ]
    q, hist, images = api_server.parse_messages(messages)
    assert q == "why?"
    assert hist == [("be brief\nwhat is this?", "a cat")]
    assert len(images) == 1 and images[0].shape == (16, 16, 3)


def test_parse_messages_system_concat_and_local_files(tmp_path):
    # Multiple system messages concatenate in order.
    q, hist, _ = api_server.parse_messages([
        {"role": "system", "content": "be terse"},
        {"role": "system", "content": "answer in French"},
        {"role": "user", "content": "hi"},
    ])
    assert q == "be terse\nanswer in French\nhi"
    # Local file paths are rejected unless explicitly allowed.
    from PIL import Image

    p = tmp_path / "x.png"
    Image.fromarray(np.zeros((8, 8, 3), np.uint8)).save(p)
    msg = [{"role": "user", "content": [
        {"type": "image_url", "image_url": {"url": str(p)}},
        {"type": "text", "text": "what?"},
    ]}]
    with pytest.raises(ValueError, match="allow-local-files"):
        api_server.parse_messages(msg)
    _, _, images = api_server.parse_messages(msg, allow_local_files=True)
    assert images[0].shape == (8, 8, 3)


def test_server_rejects_bad_max_tokens(continuous_server):
    url, _ = continuous_server
    for bad in (0, -5):
        try:
            _post(url, {
                "max_tokens": bad,
                "messages": [{"role": "user", "content": "q"}],
            })
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400


def test_parse_messages_rejects_bad_shapes():
    with pytest.raises(ValueError):
        api_server.parse_messages(
            [{"role": "assistant", "content": "hi"}]
        )
    with pytest.raises(ValueError):
        api_server.parse_messages([
            {"role": "user", "content": "q"},
            {"role": "assistant", "content": "a"},
        ])
    # Trailing system message would be silently lost — reject it.
    with pytest.raises(ValueError, match="precede a user turn"):
        api_server.parse_messages([
            {"role": "user", "content": "q"},
            {"role": "system", "content": "answer in JSON"},
        ])
    # Unsupported roles are an error, not a silent drop; tool/function
    # get a no-tool-calling message.
    with pytest.raises(ValueError, match="tool-calling"):
        api_server.parse_messages([
            {"role": "tool", "content": "output"},
            {"role": "user", "content": "q"},
        ])
    with pytest.raises(ValueError, match="unsupported message role"):
        api_server.parse_messages([
            {"role": "narrator", "content": "x"},
            {"role": "user", "content": "q"},
        ])
    # "developer" is OpenAI's alias for system.
    q, hist, _ = api_server.parse_messages([
        {"role": "developer", "content": "be brief"},
        {"role": "user", "content": "hi"},
    ])
    assert q == "be brief\nhi"


def test_server_reports_length_finish_reason(continuous_server):
    """The tiny vocab never emits the EOS id, so every decode truncates:
    finish_reason must say 'length', not 'stop'."""
    url, _ = continuous_server
    with _post(url, {
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 3,
    }) as r:
        assert json.load(r)["choices"][0]["finish_reason"] == "length"
    deltas_final = None
    with _post(url, {
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 3, "stream": True,
    }) as r:
        for line in r:
            line = line.decode().strip()
            if line.startswith("data: ") and line != "data: [DONE]":
                c = json.loads(line[6:])
                fr = c["choices"][0]["finish_reason"]
                if fr is not None:
                    deltas_final = fr
    assert deltas_final == "length"


def _tiny_pipe() -> OryxInference:
    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    return OryxInference(FakeTokenizer(), params, cfg)


@pytest.fixture(scope="module")
def continuous_server():
    """Server on the continuous-batching engine (paged KV scheduler)."""
    pipe = _tiny_pipe()
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=2, page_size=16,
        decode_chunk=4, max_ctx=512,
    )
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", pipe
    srv.scheduler.close()
    srv.shutdown()


def _post(url, body):
    req = urllib.request.Request(
        url + "/v1/chat/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=300)


def _sse_chunks(raw: str) -> list[dict]:
    """The JSON bodies of an SSE response, `[DONE]` left out."""
    return [
        json.loads(l[len("data: "):]) for l in raw.splitlines()
        if l.startswith("data: ") and l != "data: [DONE]"
    ]


def _sse_text(chunks: list[dict]) -> str:
    return "".join(
        c["choices"][0]["delta"].get("content") or ""
        for c in chunks if c.get("choices")
    )


@pytest.mark.parametrize("stream", [False, True], ids=["json", "sse"])
def test_server_completion_matches_pipeline(continuous_server, stream):
    """Non-streaming and streaming through the scheduler both return
    exactly the solo pipeline reply, with real usage accounting."""
    url, pipe = continuous_server
    ref = pipe.chat("hello there", max_new_tokens=5)
    body = {
        "model": "oryx-tpu",
        "messages": [{"role": "user", "content": "hello there"}],
        "max_tokens": 5,
    }
    if not stream:
        with _post(url, body) as resp:
            out = json.load(resp)
        assert out["object"] == "chat.completion"
        assert out["choices"][0]["message"]["content"] == ref
        assert out["choices"][0]["finish_reason"] == "length"
        # OpenAI usage accounting: real token counts, not padding.
        usage = out["usage"]
        # /v1/models and /healthz answer.
        with urllib.request.urlopen(url + "/v1/models", timeout=30) as r:
            assert json.load(r)["data"][0]["id"] == "oryx-tpu"
        with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
            assert json.load(r)["status"] == "ok"
    else:
        with _post(url, {
            **body, "stream": True,
            "stream_options": {"include_usage": True},
        }) as resp:
            raw = resp.read().decode()
        assert raw.strip().endswith("data: [DONE]")
        chunks = _sse_chunks(raw)
        deltas = _sse_text(chunks)
        assert deltas == ref
        with_usage = [c for c in chunks if c.get("usage")]
        assert len(with_usage) == 1
        usage = with_usage[0]["usage"]
    assert usage["prompt_tokens"] > 0
    assert usage["completion_tokens"] == 5
    assert usage["total_tokens"] == (
        usage["prompt_tokens"] + usage["completion_tokens"]
    )


def test_server_streaming_usage_chunk(continuous_server):
    """stream_options.include_usage: a final empty-choices chunk carries
    the usage totals; without the option, no chunk has usage."""
    url, pipe = continuous_server
    body = {
        "model": "oryx-tpu", "stream": True,
        "stream_options": {"include_usage": True},
        "messages": [{"role": "user", "content": "hello there"}],
        "max_tokens": 5,
    }
    with _post(url, body) as resp:
        raw = resp.read().decode()
    chunks = _sse_chunks(raw)
    # OpenAI contract: EVERY chunk carries the usage key — null on delta
    # chunks, totals (with empty choices) on the final one.
    assert all("usage" in c for c in chunks), chunks
    with_usage = [c for c in chunks if c["usage"] is not None]
    assert len(with_usage) == 1
    u = with_usage[-1]["usage"]
    assert with_usage[-1]["choices"] == []
    assert u["prompt_tokens"] > 0 and 0 < u["completion_tokens"] <= 5
    assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]

    body.pop("stream_options")
    with _post(url, body) as resp:
        raw = resp.read().decode()
    assert '"usage"' not in raw

    # Unsupported stream_options shapes 400 instead of silently no-oping.
    for bad in (
        {"stream": False, "stream_options": {"include_usage": True}},
        {"stream": True, "stream_options": {"includeUsage": True}},
    ):
        b = {"model": "oryx-tpu", "max_tokens": 4, **bad,
             "messages": [{"role": "user", "content": "hi"}]}
        try:
            _post(url, b).close()
            raise AssertionError(f"{bad} should have 400'd")
        except urllib.error.HTTPError as e:
            assert e.code == 400


def test_server_streaming_sse(continuous_server):
    url, pipe = continuous_server
    body = {
        "model": "oryx-tpu", "stream": True,
        "messages": [{"role": "user", "content": "hello there"}],
        "max_tokens": 5,
    }
    deltas, done = [], False
    with _post(url, body) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        for line in resp:
            line = line.decode().strip()
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                done = True
                break
            chunk = json.loads(payload)
            delta = chunk["choices"][0]["delta"]
            if "content" in delta:
                deltas.append(delta["content"])
    assert done
    assert "".join(deltas) == pipe.chat("hello there", max_new_tokens=5)


def test_server_bad_request(continuous_server):
    url, _ = continuous_server
    try:
        _post(url, {"messages": [{"role": "assistant", "content": "x"}]})
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400
        assert "invalid_request_error" in e.read().decode()


def test_parse_sampling_validation():
    assert api_server._parse_sampling({}) == {}
    s = api_server._parse_sampling({
        "temperature": 0.7, "top_p": 0.9, "stop": "###", "seed": 3,
    })
    assert s == {
        "temperature": 0.7, "top_p": 0.9, "stop": ["###"], "seed": 3,
    }
    # stop list normalizes, empties dropped
    assert api_server._parse_sampling({"stop": ["a", "", "b"]})["stop"] == [
        "a", "b"
    ]
    for bad in (
        {"n": 2},
        {"logprobs": True},
        {"temperature": -0.1},
        {"temperature": 2.5},
        {"top_p": 0.0},
        {"top_p": 1.5},
        {"stop": [1, 2]},
        {"stop": ["x"] * 9},
    ):
        with pytest.raises((ValueError, TypeError)):
            api_server._parse_sampling(bad)


def test_parse_messages_rejects_misplaced_images():
    img = np.zeros((8, 8, 3), np.uint8)
    part = {"type": "image_url", "image_url": {"url": _data_uri(img)}}
    # Image on an assistant message.
    with pytest.raises(ValueError, match="user messages"):
        api_server.parse_messages([
            {"role": "user", "content": "q"},
            {"role": "assistant", "content": [
                {"type": "text", "text": "a"}, part,
            ]},
            {"role": "user", "content": "q2"},
        ])
    # Image on a non-first user turn (would silently re-pin to turn 1).
    with pytest.raises(ValueError, match="FIRST user message"):
        api_server.parse_messages([
            {"role": "user", "content": "q"},
            {"role": "assistant", "content": "a"},
            {"role": "user", "content": [
                {"type": "text", "text": "and this?"}, part,
            ]},
        ])
    # First-turn image stays accepted.
    _, _, images = api_server.parse_messages([
        {"role": "user", "content": [
            {"type": "text", "text": "what?"}, part,
        ]},
        {"role": "assistant", "content": "a"},
        {"role": "user", "content": "why?"},
    ])
    assert len(images) == 1


def test_server_sampling_roundtrip(continuous_server):
    url, pipe = continuous_server
    body = {
        "messages": [{"role": "user", "content": "hello there"}],
        "max_tokens": 5, "temperature": 0.9, "top_p": 0.95, "seed": 7,
    }

    def ask(**over):
        with _post(url, {**body, **over}) as resp:
            return json.load(resp)["choices"][0]["message"]["content"]

    # The sampling fields reach the slot: a seeded request reproduces
    # its sample, and the draw is the seed's (the engine keys each
    # request by its own seed, so the sample is not the dense
    # `pipe.chat` loop's draw for the same seed).
    reply = ask()
    assert reply == ask()
    assert reply != pipe.chat("hello there", max_new_tokens=5)
    assert len({reply, ask(seed=8), ask(seed=9)}) > 1
    # Unsupported n > 1 is a 400, not a silent ignore.
    try:
        _post(url, {
            "messages": [{"role": "user", "content": "q"}], "n": 2,
        })
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_server_rejects_excessive_max_tokens(continuous_server):
    url, _ = continuous_server
    try:
        _post(url, {
            "max_tokens": 10**9,
            "messages": [{"role": "user", "content": "q"}],
        })
        raise AssertionError("expected HTTP 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400


def _parse_prometheus(text: str) -> dict[str, float]:
    """Well-formedness check + name->value map (labels folded in)."""
    import re

    values = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert re.match(r"^# TYPE \S+ (counter|gauge|histogram)$",
                            line), line
            continue
        m = re.match(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})? (-?[\d.e+-]+|inf)$",
                     line)
        assert m, f"malformed metrics line: {line!r}"
        values[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return values


def test_metrics_endpoint_under_concurrent_load(continuous_server):
    """Load test: >= 5 simultaneous clients (streaming +
    non-streaming) through the scheduler, then GET /metrics must return
    well-formed Prometheus text with the serving counters/histograms."""
    url, pipe = continuous_server
    qs = [("hello there", 4), ("what now?", 6), ("tell me more", 5),
          ("and then?", 4)]
    stream_qs = [("say something", 5)]
    errors: list[str] = []

    def nonstream(q, c):
        try:
            with _post(url, {
                "max_tokens": c,
                "messages": [{"role": "user", "content": q}],
            }) as resp:
                json.load(resp)
        except Exception as e:
            errors.append(f"{q}: {e!r}")

    def stream(q, c):
        try:
            with _post(url, {
                "max_tokens": c, "stream": True,
                "messages": [{"role": "user", "content": q}],
            }) as resp:
                resp.read()
        except Exception as e:
            errors.append(f"{q}: {e!r}")

    threads = [
        threading.Thread(target=nonstream, args=qc) for qc in qs
    ] + [threading.Thread(target=stream, args=qc) for qc in stream_qs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "client hung"
    assert not errors, errors

    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    values = _parse_prometheus(text)
    assert values["oryx_serving_admitted"] >= 5
    assert values["oryx_serving_completed"] >= 5
    assert "oryx_serving_slot_occupancy" in values
    assert "oryx_serving_queue_depth" in values
    assert values["oryx_serving_ttft_seconds_count"] >= 5
    assert values["oryx_serving_time_per_output_token_seconds_count"] > 0
    # Histogram buckets are cumulative and end at the total count.
    ttft_inf = values['oryx_serving_ttft_seconds_bucket{le="+Inf"}']
    assert ttft_inf == values["oryx_serving_ttft_seconds_count"]
    # Wasted + useful partition the total.
    assert (
        values["oryx_serving_decode_steps_useful"]
        + values["oryx_serving_decode_steps_wasted"]
        == values["oryx_serving_decode_steps_total"]
    )


def _timeline(url) -> dict:
    with urllib.request.urlopen(url + "/debug/timeline?n=0",
                                timeout=30) as r:
        return json.load(r)


def _ask_concurrently(url, bodies: list[dict]) -> list[str]:
    """POST every body from its own thread at once; the reply text of
    each (SSE deltas joined for `stream` bodies), in `bodies` order.
    Also asserts that the requests really shared the engine: some
    dispatch of this burst decoded more than one live slot."""
    steps_before = _timeline(url)["total_steps"]
    results: list[str | None] = [None] * len(bodies)
    errors: list[str] = []

    def call(i):
        try:
            with _post(url, bodies[i]) as resp:
                if not bodies[i].get("stream"):
                    results[i] = json.load(
                        resp
                    )["choices"][0]["message"]["content"]
                    return
                raw = resp.read().decode()
            results[i] = _sse_text(_sse_chunks(raw))
        except Exception as e:  # surface in the main thread
            errors.append(f"{bodies[i]}: {e!r}")

    threads = [
        threading.Thread(target=call, args=(i,))
        for i in range(len(bodies))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "client hung"
    assert not errors, errors
    tl = _timeline(url)
    burst = tl["records"][:tl["total_steps"] - steps_before]
    assert max(r["live_slots"] for r in burst) > 1, burst
    return results


def _ask(q, cap, **extra) -> dict:
    return {
        "max_tokens": cap,
        "messages": [{"role": "user", "content": q}], **extra,
    }


def test_server_concurrent_mixed_clients(continuous_server):
    """>=8 genuinely simultaneous HTTP clients —
    mixed stream/non-stream, mixed text/image — through the
    ThreadingHTTPServer into two slots. Every response must equal
    its single-request answer and the requests must have actually
    shared dispatches (the engine is not just running solo rows)."""
    url, pipe = continuous_server
    rng = np.random.default_rng(7)
    imgs = [
        rng.integers(0, 255, size=(24, 24, 3), dtype=np.uint8)
        for _ in range(2)
    ]
    text_qs = [("hello there", 4), ("what now?", 6),
               ("tell me more", 8), ("and then?", 5)]
    img_qs = [("what is this?", 4), ("describe it", 6)]
    stream_qs = [("say something", 5), ("go on", 7)]
    # Single-request references (greedy decode: order-independent).
    refs = [pipe.chat(q, max_new_tokens=c) for q, c in text_qs]
    refs += [
        pipe.chat(q, images=[im], max_new_tokens=c)
        for (q, c), im in zip(img_qs, imgs)
    ]
    refs += [
        "".join(pipe.chat_stream(q, max_new_tokens=c))
        for q, c in stream_qs
    ]
    bodies = [_ask(q, c) for q, c in text_qs]
    bodies += [
        _ask([
            {"type": "text", "text": q},
            {"type": "image_url", "image_url": {"url": _data_uri(im)}},
        ], c)
        for (q, c), im in zip(img_qs, imgs)
    ]
    bodies += [_ask(q, c, stream=True) for q, c in stream_qs]
    assert len(bodies) == 8
    assert _ask_concurrently(url, bodies) == refs


@pytest.mark.parametrize(
    "caps", [(16, 16, 16), (12, 20, 28)],
    ids=["equal-caps", "mixed-caps"],
)
def test_concurrent_requests_match_solo_across_max_tokens(
    continuous_server, caps,
):
    """Concurrent non-streaming requests, resident together, each
    return exactly what a solo `pipe.chat` with that request's own
    `max_tokens` returns: a slot retires at its own cap whatever its
    neighbours asked for. (Several chunks long, so that they overlap.)"""
    url, pipe = continuous_server
    qs = ["hello there", "what now?", "tell me more"]
    refs = [pipe.chat(q, max_new_tokens=c) for q, c in zip(qs, caps)]
    assert _ask_concurrently(
        url, [_ask(q, c) for q, c in zip(qs, caps)]
    ) == refs


def test_concurrent_requests_match_solo_across_sampling_params(
    continuous_server,
):
    """Requests that differ in temperature / top_p / stop share one
    resident batch (sampling is per slot, nothing is split off) and
    each still equals its solo output."""
    url, pipe = continuous_server
    greedy = pipe.chat("hello there", max_new_tokens=16)
    # A stop string the greedy reply is sure to contain.
    stop = greedy[2:4]
    asks = [
        ("hello there", {}),
        ("hello there", {"stop": [stop]}),
        ("what now?", {"temperature": 0.9, "top_p": 0.95, "seed": 7}),
        ("tell me more", {"temperature": 0.5, "seed": 3}),
    ]
    bodies = [_ask(q, 16, **kw) for q, kw in asks]
    # Solo: the dense loop for the greedy rows; for the seeded rows the
    # same request alone through the server (the engine keys a request
    # by its own seed, not by the dense loop's draw order).
    refs = [pipe.chat(q, max_new_tokens=16, **kw) for q, kw in asks[:2]]
    assert refs[1] != refs[0] and stop not in refs[1]
    for body in bodies[2:]:
        with _post(url, body) as resp:
            refs.append(json.load(resp)["choices"][0]["message"]["content"])
    assert _ask_concurrently(url, bodies) == refs


def test_default_engine_is_continuous():
    """`build_server` with no `engine` builds the scheduler the
    benchmark measures, and `/metrics` says so."""
    from oryx_tpu.serve.scheduler import ContinuousScheduler

    pipe = _tiny_pipe()
    srv = api_server.build_server(pipe, port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        assert isinstance(srv.scheduler, ContinuousScheduler)
        url = f"http://127.0.0.1:{srv.server_address[1]}"
        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            text = r.read().decode()
        assert 'engine="continuous"' in next(
            l for l in text.splitlines()
            if l.startswith("oryx_serving_build_info{")
        )
    finally:
        srv.scheduler.close()
        srv.shutdown()


def test_window_engine_is_unknown():
    """The registry's own error names what is registered."""
    with pytest.raises(ValueError, match="unknown engine 'window'"):
        api_server.build_server(None, port=0, engine="window")


@pytest.mark.parametrize("flag", [
    ["--batch-window", "0.1"], ["--max-batch", "4"],
    ["--engine", "window"],
])
def test_cli_refuses_the_window_engines_flags(flag):
    with pytest.raises(SystemExit) as e:
        api_server.main(["--model-path", "x", *flag])
    assert e.value.code == 2


@pytest.mark.parametrize("flag", [
    ["--fuse-steps", "4"], ["--fuse-steps", "auto"],
])
def test_cli_refuses_removed_flags(flag, capsys):
    """The K-step megastep went with its flag (PR 48): the spelling is
    an error, not accepted and ignored. `--decode-chunk` sets how many
    forwards a dispatch runs."""
    with pytest.raises(SystemExit) as e:
        api_server.main(["--model-path", "x", "--ragged", *flag])
    assert e.value.code == 2
    assert "unrecognized arguments: --fuse-steps" in capsys.readouterr().err


def test_build_server_refuses_the_removed_keyword():
    with pytest.raises(TypeError, match="fuse_steps"):
        api_server.build_server(None, port=0, fuse_steps=2)


def test_continuous_request_id_and_debug_endpoints(continuous_server):
    """Acceptance: a request through --engine continuous yields (a) an
    X-Request-Id header, (b) a /debug/trace?id= span tree covering
    queue-wait -> prefill -> decode chunks -> emission as loadable
    Chrome trace JSON, and (c) a flight-recorder entry in
    /debug/requests."""
    url, pipe = continuous_server
    with _post(url, {
        "messages": [{"role": "user", "content": "hello there"}],
        "max_tokens": 5,
    }) as r:
        rid = r.headers["X-Request-Id"]
        out = json.load(r)
    assert rid
    # The completion id embeds the request id (client-side join key).
    assert out["id"] == f"chatcmpl-{rid}"

    with urllib.request.urlopen(url + "/debug/requests", timeout=30) as r:
        recorder = json.load(r)
    entry = next(
        e for e in recorder["requests"] if e["id"] == rid
    )
    assert entry["done"] and entry["kind"] == "request"
    assert entry["meta"]["finish_reason"] == "length"
    assert entry["meta"]["completion_tokens"] == 5
    assert entry["num_spans"] >= 4

    with urllib.request.urlopen(
        url + f"/debug/trace?id={rid}", timeout=30
    ) as r:
        assert r.headers["X-Request-Id"] == rid
        tracejs = json.load(r)
    events = tracejs["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    # Perfetto-loadable complete events: required keys, µs timestamps.
    for e in xs:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    names = [e["name"] for e in xs]
    for want in ("queue_wait", "admission", "prompt_prep", "prefill",
                 "decode_chunk", "emission"):
        assert want in names, (want, names)
    # Spans are causally ordered: queue_wait starts first.
    first = min(xs, key=lambda e: e["ts"])
    assert first["name"] == "queue_wait"
    assert tracejs["request"]["id"] == rid

    # Unknown / missing ids fail cleanly.
    for path, code in (("/debug/trace?id=deadbeef", 404),
                       ("/debug/trace", 400)):
        try:
            urllib.request.urlopen(url + path, timeout=30)
            raise AssertionError(f"expected HTTP {code}")
        except urllib.error.HTTPError as e:
            assert e.code == code


def test_continuous_streaming_request_id(continuous_server):
    """SSE streams carry the X-Request-Id header and the chunk ids
    embed it; the trace is recorded like a non-streaming request."""
    url, _ = continuous_server
    with _post(url, {
        "messages": [{"role": "user", "content": "hello there"}],
        "max_tokens": 4, "stream": True,
    }) as r:
        rid = r.headers["X-Request-Id"]
        raw = r.read().decode()
    assert rid
    chunks = _sse_chunks(raw)
    assert all(c["id"] == f"chatcmpl-{rid}" for c in chunks)
    with urllib.request.urlopen(
        url + f"/debug/trace?id={rid}", timeout=30
    ) as r:
        names = {
            e["name"] for e in json.load(r)["traceEvents"]
            if e.get("ph") == "X"
        }
    assert {"queue_wait", "prefill", "decode_chunk"} <= names


def test_metrics_content_type_and_build_info(continuous_server):
    """Satellite: /metrics serves the exact Prometheus exposition
    content type, every name is oryx_serving_-prefixed, and the
    build_info gauge carries revision + engine labels."""
    import re

    url, _ = continuous_server
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        assert r.headers["Content-Type"] == "text/plain; version=0.0.4"
        text = r.read().decode()
    # oryx_pool_/oryx_page_ (page-pool observatory),
    # oryx_device_time_/oryx_profile_ (device-time attributor),
    # oryx_audit_/oryx_numerics_ (output-quality observatory) and
    # oryx_cache_ (the prefix cache's host spill tier) are
    # raw-named like oryx_anomaly_: engine-independent semantics.
    allowed = ("oryx_serving_", "oryx_anomaly_", "oryx_pool_",
               "oryx_page_", "oryx_device_time_", "oryx_profile_",
               "oryx_audit_", "oryx_numerics_", "oryx_cache_")
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert line.startswith(allowed), line
    m = re.search(
        r'^oryx_serving_build_info\{([^}]*)\} 1$', text, re.M
    )
    assert m, text
    labels = m.group(1)
    assert 'engine="continuous"' in labels
    assert 'revision="' in labels and 'revision=""' not in labels
    assert 'model="oryx-tpu"' in labels


def test_debug_requests_limit_and_state_filters(continuous_server):
    """Satellite: /debug/requests stays usable during a load sweep —
    ?limit= bounds the response, ?state= filters by lifecycle, bad
    values are 400s, and finished entries carry the full cost
    ledger."""
    from oryx_tpu.utils.metrics import REQUEST_COST_KEYS

    url, _ = continuous_server
    for i in range(3):
        with _post(url, {
            "messages": [{"role": "user", "content": f"filter q {i}"}],
            "max_tokens": 3,
        }) as r:
            json.load(r)

    with urllib.request.urlopen(
        url + "/debug/requests", timeout=30
    ) as r:
        full = json.load(r)
    assert full["total"] == full["returned"] == len(full["requests"])
    assert full["total"] >= 3

    with urllib.request.urlopen(
        url + "/debug/requests?limit=2", timeout=30
    ) as r:
        lim = json.load(r)
    assert lim["returned"] == len(lim["requests"]) == 2
    assert lim["total"] == full["total"]  # total counts pre-limit
    # Newest-first order is preserved under limit.
    assert [e["id"] for e in lim["requests"]] == [
        e["id"] for e in full["requests"][:2]
    ]

    with urllib.request.urlopen(
        url + "/debug/requests?state=done&limit=5", timeout=30
    ) as r:
        done = json.load(r)
    assert done["requests"], "no finished requests recorded"
    for e in done["requests"]:
        assert e["done"] and "error" not in e["meta"]
        cost = e["meta"].get("cost")
        assert cost and set(REQUEST_COST_KEYS) <= set(cost), e

    with urllib.request.urlopen(
        url + "/debug/requests?state=active", timeout=30
    ) as r:
        active = json.load(r)
    for e in active["requests"]:
        assert not e["done"]

    for bad in ("?state=bogus", "?limit=-1", "?limit=x"):
        try:
            urllib.request.urlopen(
                url + "/debug/requests" + bad, timeout=30
            )
            raise AssertionError(f"{bad}: expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400


def test_cost_ledger_in_completion_and_final_sse_chunk(continuous_server):
    """Tentpole surface: the per-request cost ledger rides the
    non-streaming completion body and the final SSE chunk under
    "oryx", with prefill + cached partitioning the prompt."""
    from oryx_tpu.utils.metrics import REQUEST_COST_KEYS

    url, _ = continuous_server
    with _post(url, {
        "messages": [{"role": "user", "content": "cost ledger body"}],
        "max_tokens": 4,
    }) as r:
        out = json.load(r)
    cost = out["oryx"]["cost"]
    assert set(REQUEST_COST_KEYS) <= set(cost)
    assert (
        cost["prefill_tokens"] + cost["cached_tokens"]
        == out["usage"]["prompt_tokens"]
    )
    assert cost["page_seconds"] > 0

    with _post(url, {
        "messages": [{"role": "user", "content": "cost ledger sse"}],
        "max_tokens": 4, "stream": True,
        "stream_options": {"include_usage": True},
    }) as r:
        raw = r.read().decode()
    chunks = _sse_chunks(raw)
    with_cost = [c for c in chunks if "oryx" in c]
    assert len(with_cost) == 1
    fin = with_cost[0]
    # The ledger rides the FINISH chunk (the one carrying
    # finish_reason), before any usage-totals chunk.
    assert fin["choices"][0]["finish_reason"] is not None
    assert set(REQUEST_COST_KEYS) <= set(fin["oryx"]["cost"])
    assert fin["oryx"]["cost"]["decode_steps"] >= 4


def test_concurrent_metrics_scrapes_during_load(continuous_server):
    """Satellite: /metrics scraped in parallel WHILE the engine is
    decoding — every exposition must be well-formed (no torn lines, no
    duplicate families) and every histogram internally consistent
    (cumulative buckets, +Inf == _count)."""
    url, _ = continuous_server
    errors: list[str] = []
    done = threading.Event()

    def client(i: int) -> None:
        try:
            with _post(url, {
                "max_tokens": 6,
                "messages": [
                    {"role": "user", "content": f"scrape load {i}"}
                ],
            }) as r:
                json.load(r)
        except Exception as e:
            errors.append(f"client {i}: {e!r}")

    def scraper() -> None:
        import re as re_lib

        while not done.is_set():
            try:
                with urllib.request.urlopen(
                    url + "/metrics", timeout=30
                ) as r:
                    text = r.read().decode()
                values = _parse_prometheus(text)  # asserts line shape
                # Histogram internal consistency within ONE scrape.
                fams = {
                    m.group(1)
                    for line in text.splitlines()
                    if (m := re_lib.match(r"^(\S+)_bucket\{", line))
                }
                for fam in fams:
                    cum = [
                        v for k, v in values.items()
                        if k.startswith(f"{fam}_bucket{{")
                    ]
                    assert cum, fam
                    inf = values[f'{fam}_bucket{{le="+Inf"}}']
                    assert inf == values[f"{fam}_count"], fam
                    assert max(cum) == inf, fam
            except Exception as e:
                errors.append(f"scraper: {e!r}")
                return

    clients = [
        threading.Thread(target=client, args=(i,)) for i in range(4)
    ]
    scrapers = [threading.Thread(target=scraper) for _ in range(3)]
    for t in scrapers + clients:
        t.start()
    for t in clients:
        t.join(timeout=600)
    done.set()
    for t in scrapers:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in clients + scrapers), "hung"
    assert not errors, errors
