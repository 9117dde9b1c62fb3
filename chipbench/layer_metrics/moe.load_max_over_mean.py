"""How unevenly the router loads the experts: the most (token, choice)
pairs an expert got over the window, over the mean (the program's own
``moe_expert_load``, summed over the expert layers). 1 is even; the
grouped matmul's longest group and an expert-parallel layout's slowest
chip follow it."""

from chipbench.layer_metrics import _latent_ops as L


def read(run):
    load = L.section_delta(run, "expert_layer", "moe_expert_load")
    if not load or not sum(load):
        return None
    return max(load) / (sum(load) / len(load))
