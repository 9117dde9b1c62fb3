"""Tokens of the steps whose loss was fetched and whose offsets were
committed inside the window, over the whole window."""


def read(run):
    toks = sum(s["tokens"] for s in run["steps"] if s["committed"])
    return toks / run["window_s"]
