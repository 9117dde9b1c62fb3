"""Host time the stream's producer thread spends on one batch, in the
traced part of the window: the polls that returned records (a transform
follows them before the next poll), the transforms and the device
transfers, summed, over the batches shipped. Against the step's time it
says how near ingest is to blocking; ``batch_wait_ms.train`` only says
that it does not."""

from chipbench.layer_metrics import _named


def polls_with_records(tr) -> float:
    """Seconds of the ``tk_stream:poll`` spans that a transform follows
    before the next poll starts: an empty poll is the producer waiting
    for the topic, not working."""
    polls = sorted(tr["host_spans"].get("tk_stream:poll", ()))
    starts = sorted(s for s, _d in tr["host_spans"].get("tk_stream:transform", ()))
    total = 0.0
    for i, (s, d) in enumerate(polls):
        nxt = polls[i + 1][0] if i + 1 < len(polls) else float("inf")
        if any(s + d <= t < nxt for t in starts):
            total += d
    return total


def read(run):
    shipped = _named.durations(run, "tk_stream:to_device")
    if not shipped:
        return None
    work = (
        polls_with_records(run["trace"])
        + sum(_named.durations(run, "tk_stream:transform"))
        + sum(shipped)
    )
    return 1e3 * work / len(shipped)
