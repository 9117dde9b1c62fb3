"""Share of the window's slot-ticks that produced a served token: the
tokens the ticks surfaced inside the window (a request's first token is
the admission's, not a tick's) over slots x ticks a sync x the tick blocks
the server ran (its own count, ``ServeMetrics.tick_time``). A slot held by
a request that is past its budget, or empty until the next admission,
ticks for nothing. (Slot-seconds from ``slot_active`` to ``finished``, as
ISSUE 23 had it, read 99% whatever happens: a slot stays held until the
sync that retires its request, and is refilled at once.)"""


def read(run):
    counters = run["counters"]
    blocks = counters[-1]["ticks"] - counters[0]["ticks"]
    if blocks <= 0:
        return None
    t0, t1 = run["t0"], run["t_close"]
    toks = 0
    for r in run["requests"]:
        for i, (t, n) in enumerate(r["syncs"]):
            if t0 <= t <= t1:
                toks += n - 1 if i == 0 else n
    ticks = run["conf"]["deployment"]["ticks_per_sync"] * blocks
    return 100.0 * toks / (run["slots"] * ticks)
