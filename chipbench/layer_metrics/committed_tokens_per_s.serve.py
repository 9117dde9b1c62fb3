"""Output tokens of the completions that finished and whose offsets the
cadence of commits had made durable inside the window (before the flush
that closes it), over the whole window. The ledger commits in offset
order, so a long request at a low offset holds its partition's watermark
and this count trails ``serve.tokens_per_s`` by the requests still open
and by those waiting behind them: how far is the commit path's share."""


def read(run):
    t0, t1 = run["t0"], run["t_before_flush"]
    toks = sum(
        r["n_tokens"] for r in run["requests"]
        if r["finished"] is not None and r["committed"] is not None
        and t0 <= r["committed"] <= t1
    )
    return toks / run["window_s"]
