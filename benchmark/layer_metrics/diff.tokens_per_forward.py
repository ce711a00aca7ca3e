"""Model step: tokens a block dispatch fixed per slot-forward that had
work to do, `diffusion_tokens_unmasked_total / decode_steps_useful`
over the window: a block of B tokens costs a live slot T denoising
forwards (its commit rides the next block's first, since PR 39), so
B / T (2 at B 4, T 2; B / (T + 1), 4/3, where a forward of its own
commits, the program before PR 39); less where the dynamic rule needs
more forwards than tokens allow, or a prompt's tail leaves fewer than B
positions to fill.

Reads run["counters"] (the /metrics delta). None where the program has
no such counter (a program before PR 26) or nothing was dispatched."""
LAYER = "model step"


def read(run):
    c = run["counters"]
    useful = c.get("decode_steps_useful")
    toks = c.get("diffusion_tokens_unmasked_total")
    return toks / useful if useful and toks is not None else None
