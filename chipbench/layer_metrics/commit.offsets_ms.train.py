"""``tk_commit:offsets`` (the broker's offset commit inside
``CommitToken.commit``) in the traced part of the window, median."""

from chipbench.layer_metrics import _named


def read(run):
    return _named.median_ms(run, "tk_commit:offsets")
