"""The share of (token, choice) pairs that chose a zero-compute expert:
the program's own ``moe_zero_assignments`` over ``moe_assignments`` in
the window. A router that spread its top-k evenly over E real and Z zero
outputs reads ``Z / (E + Z)`` (33.3% at 512 + 256); the share is compute
the model saves itself, and every chip pays it alike."""

from chipbench.layer_metrics import _latent_ops as L


def read(run):
    zero = L.section_delta(run, "expert_layer", "moe_zero_assignments")
    pairs = L.section_delta(run, "expert_layer", "moe_assignments")
    if zero is None or not pairs:
        return None
    return 100.0 * zero / pairs
