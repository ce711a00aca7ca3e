"""Parent side of the mixed-queue cell: a closed loop of single-turn
requests over a text-only window / global hybrid, four in five SHORT
(a question) and one in five LONG (a pasted document and a question
behind it), in one queue. The shape of runners/serve_reasoning.py's
`run` (child holds the chip, traffic made meanwhile, every shape
warmed, window, scrape, reduce, then the comparison on what the window
served), which it runs as it is with two names of its own in place:

  - `mixed_requests`: the two kinds' lengths are mid-quantiles of their
    distributions (`traffic.quantile_values`), each list shuffled ONCE
    by the mix's `order_seed`, and dealt in ONE fixed order: every
    `long_every`-th request is long. `client_lists` deals the requests
    to clients in turn and client i of n starts i/n of the way through
    its list AT EVERY SEED: the seed makes the words and the weights,
    never the order (serve_latent's docstring says why).
  - the child is `serve_mixedq_child.py` (its configuration keys, its
    sample, its comparison; the traced slice's window on the device's
    clock, as serve_reasoning_child's).
  - no prefix cache, so no copy-on-write program to warm; the warm-up
    sends one prompt inside every embed bucket up to the longest
    document, which passes through every block-table width.
  - `correct` is decided AFTER the window, on what it served: the
    `stop` that ends the child is answered with a `logit_check` event
    (correctness_smallthinker.py). Nothing of it is inside `setup_s`.

Never imports jax."""

from __future__ import annotations

import random

from benchmark import traffic
from benchmark.runners import serve


class Child(serve.Child):
    script = "serve_mixedq_child.py"


def mixed_requests(p: dict, seed: int, n: int) -> list[dict]:
    """n request bodies in the mix's one fixed order: request i is LONG
    (a document at the head of the user turn, a question behind it)
    iff i % long_every == long_every - 1, else SHORT (a question)."""
    words = random.Random(seed)
    order = random.Random(p.get("order_seed", 0))
    every = p["long_every"]
    n_long = n // every
    draw = lambda dist, k: traffic.shuffled(  # noqa: E731
        traffic.quantile_values(dist, max(1, k)), order)
    short = draw(p["user_tokens"], n - n_long)
    docs = draw(p["document_tokens"], n_long)
    asks = draw(p["question_tokens"], n_long)
    outs = draw(p["max_tokens"], n)
    system = traffic.text_of(random.Random(7), p.get("system_tokens", 0))
    head = [{"role": "system", "content": system}] if system else []
    bodies, s, l = [], 0, 0
    for i in range(n):
        if i % every == every - 1:
            user = (traffic.text_of(words, docs[l]) + " "
                    + traffic.text_of(words, asks[l]))
            l += 1
        else:
            user = traffic.text_of(words, short[s])
            s += 1
        total = len(system) + len(user) + outs[i]
        assert total <= p["max_session_tokens"], (i, total)
        bodies.append(traffic.chat_body(
            head + [{"role": "user", "content": user}], outs[i]))
    return bodies


def client_lists(p: dict, seed: int, seconds: float) -> list[list]:
    """What each client sends in the window: see the module's
    docstring."""
    clients = p["clients"]
    n = int(clients * seconds * p.get("max_requests_per_client_s", 1.0))
    per_client = [[] for _ in range(clients)]
    for i, body in enumerate(mixed_requests(p, seed, n)):
        per_client[i % clients].append(body)
    return [traffic.rotated(c, i * len(c) // clients)
            for i, c in enumerate(per_client)]


def run(ctx: dict) -> dict:
    """serve_reasoning.run, as it is, around this cell's child and this
    cell's client lists (it looks both up in its module when it runs;
    one run a process): the warm-up, the window, the traced slice, the
    scrape, the reduction and the problems it names are that cell's,
    not a copy of them."""
    from benchmark.runners import serve_reasoning as base

    theirs = base.Child, base.client_lists
    base.Child, base.client_lists = Child, client_lists
    try:
        return base.run(ctx)
    finally:
        base.Child, base.client_lists = theirs
