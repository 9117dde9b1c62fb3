"""Device time of the latent pool's decode read, one layer of one tick:
the tick program's operations that read the pool (its scatter, whose
result is the pool, and the pool's layout copies apart) and those over
the read's scores (``chipbench/kernels/mla.py``)."""

from chipbench.layer_metrics import _latent_ops as L


def seconds(run):
    k = L.kernels(run, "mla")
    pool = k.pool_pattern(run["conf"])
    return L.seconds(
        run, f"{pool}|{k.scores_pattern(run['conf'])}", result_not=pool
    )


def read(run):
    if not run.get("trace"):
        return None
    s = seconds(run)
    calls = L.ticks_traced(run) * run["conf"]["num_hidden_layers"]
    return 1e6 * s / calls if s and calls else None
