"""Device time of one decode tick, in the steady cell: it moves the time per output token."""

from chipbench.layer_metrics import _programs

read = _programs.tick_ms
