"""Host time inside ``tk_serve:admit`` in the traced part of the window,
for each request admitted there."""


def read(run):
    tr = run["trace"]
    spans = tr["host_spans"].get("tk_serve:admit") if tr else None
    if not spans:
        return None
    admitted = sum(
        1 for r in run["requests"]
        if r["active"] is not None and tr["host_t0"] <= r["active"] <= tr["host_t1"]
    )
    if not admitted:
        return None
    return 1e3 * sum(d for _s, d in spans) / admitted
