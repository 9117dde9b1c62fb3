"""Device time a decode tick spends in the expert matmuls (all layers) of
a model whose experts lie in stacks of every layer's: the tick program's
operations that read those stacks (the three products of every tile of
the compacted form; the gather of a tile's rows and the weighted scatter
back are not among them)."""

from chipbench.layer_metrics import _moe_stack


def read(run):
    _k, s = _moe_stack.seconds_a_tick(run)
    return 1e3 * s if s else None
