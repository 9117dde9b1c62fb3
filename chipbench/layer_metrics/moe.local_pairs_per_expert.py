"""(Token, choice) pairs that met an expert held here, a held expert a
layer a tick: the program's own ``moe_local_assignments`` in the window
over the held experts (``experts_held`` in its summary), the
configuration's layers and the ticks run. It is what sizes the tiles of
the held experts' matmul (a tick of 128 rows reads 2; a deployment's chip
at 128 local slots would see 64)."""

from chipbench.layer_metrics import _latent_ops as L


def read(run):
    local = L.section_delta(run, "expert_layer", "moe_local_assignments")
    held = run["counters"][-1].get("expert_layer", {}).get("experts_held")
    ticks = L.ticks_in_window(run)
    if local is None or not held or not ticks:
        return None
    return local / (held[1] * run["conf"]["num_hidden_layers"] * ticks)
