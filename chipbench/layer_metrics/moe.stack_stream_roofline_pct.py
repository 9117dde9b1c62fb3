"""The expert matmuls' share of the memory roofline in a decode tick, for
a model whose experts lie in stacks of every layer's: the bytes of the
experts the program counted as touched (over the window, a tick), over the
matmuls' time a tick in the trace and the chip's peak bytes a second. An
expert read a second time for a second tile of rows spends time on bytes
that are not counted."""

from chipbench.layer_metrics import _latent_ops as L
from chipbench.layer_metrics import _moe_stack


def read(run):
    k, s = _moe_stack.seconds_a_tick(run)
    touched = L.section_delta(run, "expert_layer", "moe_experts_touched")
    in_window = L.ticks_in_window(run)
    if not s or not touched or not in_window:
        return None
    need = k.stream_bytes(run["conf"], touched / in_window)
    return 100.0 * need / (s * run["peaks"]["hbm_bytes_s"])
