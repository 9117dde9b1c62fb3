"""Process start to the window opening: interpreter, imports, weights,
compilation or the cache's reads, warm-up, the first checked steps."""


def read(run):
    return run["setup_s"]
