"""Vision front end: engine-thread seconds of prompt preparation per
admitted request over the window, ms: the `prompt_prep` phase
(`pipe._prepare_request`: chat template, tokenising, video sentinels)
plus the `embed` phase (`pipe._prompt_embeds`: resize and patchify into
the packed buffer, staging, the enqueue of `mm_embeds` or of the
embedding gather), `engine_phase_seconds_total{phase=}` / `admitted`.
Both run on the engine thread, which dispatches nothing else meanwhile;
the device's share of the front end is `vision.encode_ms`.

Reads run["counters"]. None when the program has no phase counter (a
parent before PR 24) or nothing was admitted; a KeyError (the run is
then incorrect, by name) when `admitted` is gone."""
LAYER = "vision front end"


def read(run):
    c = run["counters"]
    if "engine_phase_seconds_total" not in c:
        return None
    n = c["admitted"]
    prep_s = sum(
        c.get('engine_phase_seconds_total{phase="%s"}' % p, 0.0)
        for p in ("prompt_prep", "embed")
    )
    return 1e3 * prep_s / n if n else None
