"""Host time the serving loop spends between two syncs, in the traced
part of the window: every ``tk_serve:*`` span but the sync itself (poll,
admission's preparation and dispatch, tick dispatch, retirement, output
flush, offset commit), summed, over the syncs. The device waits for most
of it: nothing is queued behind a sync until the next dispatch."""

from chipbench.layer_metrics import _named

BETWEEN_SYNCS = (
    "retire", "output_flush", "commit", "poll", "admit_prep", "admit", "tick",
)


def read(run):
    return _named.ms_per_sync(run, BETWEEN_SYNCS)
