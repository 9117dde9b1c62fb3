"""Device time a decode tick spends in the routed and the shared expert
matmuls (all expert layers): the tick program's operations that read the
expert groups' weights, told by their operand shapes
(``chipbench/kernels/moe.py::operand_pattern``)."""

from chipbench.layer_metrics import _latent_ops as L


def read(run):
    k = L.kernels(run, "moe")
    s = L.seconds(run, k.operand_pattern(run["conf"]))
    ticks = L.ticks_traced(run) if s else 0
    return 1e3 * s / ticks if ticks else None
