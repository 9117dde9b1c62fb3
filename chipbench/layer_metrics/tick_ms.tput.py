"""Device time of one decode tick, in the backlog cell: it moves tokens a second."""

from chipbench.layer_metrics import _programs

read = _programs.tick_ms
