"""Scheduler: p90 of time to first token over every request issued in
the window of the traced run, ms (Harrell-Davis; from the due instant
in an open loop, from the send in a closed one; a request still
waiting at the window's end counts with the wait it has had). NOT an
end-to-end metric: the chat cell's window holds ~58 requests and this
tail spreads 7-10 % between runs of one seed, and in a saturated closed
loop it is clients / throughput (PERF.md section 2); recorded for the
ledger. In the split engine admission (prefill chunks) and decoding
share one loop, so what changes the one changes the other."""
LAYER = "scheduler"


def read(run):
    return run["requests"]["ttft_p90_ms"]
