"""Output tokens that the server surfaced inside the window, over the whole
window: every token of an answer, within its budget, that a host sync
showed between the window's opening and its close (the close comes after
the last flush of commits). All the useful work of the window and all its
time: the tokens of requests still decoding at the close are work the
window did, and the ticks a slot spends past its request's budget or empty
are not tokens. Counting only the completions that finished would leave
out a seventh of the window's work (48 slots in flight at the close) and
swing by 6% with the order of the requests (PERF.md). That every finished
completion is published once, that the cadence of commits is the
configuration's, and that each commit reaches as far as the in-order
watermark can, is held by ``correct``, not by this count; the tokens the
cadence had committed stand beside it as ``committed_tokens_per_s.serve``."""


def read(run):
    t0, t1 = run["t0"], run["t_close"]
    toks = sum(
        n for r in run["requests"] for t, n in r["syncs"] if t0 <= t <= t1
    )
    return toks / run["window_s"]
