"""Model step: share of a block dispatch's forwards that only commit, %:
`diffusion_forwards_total{kind="commit"}` / all forwards over the
window. 1 / (T + 1) where every block pays a forward of its own to
write its K/V (33.3 % at T 2, the program before PR 39); 0 since PR 39,
where a block's commit rides the next block's first denoising forward
and no forward only commits. What is still to take off the step.

Reads run["counters"]. None where the program has no such counter."""
LAYER = "model step"


def read(run):
    c = run["counters"]
    commit = c.get('diffusion_forwards_total{kind="commit"}')
    total = c.get("diffusion_forwards_total")
    return 100.0 * commit / total if total and commit is not None else None
