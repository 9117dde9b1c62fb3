"""The expert matmuls' share of the memory roofline in a decode tick: the
bytes of the experts the program counted as touched (over the window, a
tick) and of the shared experts, over the matmuls' time a tick in the
trace and the chip's peak bytes a second. The all-experts form streams
untouched experts too; their bytes are not needed and not counted."""

from chipbench.layer_metrics import _latent_ops as L


def read(run):
    k = L.kernels(run, "moe")
    s = L.seconds(run, k.operand_pattern(run["conf"]))
    touched = L.section_delta(run, "expert_layer", "moe_experts_touched")
    ticks, in_window = (L.ticks_traced(run) if s else 0), L.ticks_in_window(run)
    if not ticks or not touched or not in_window:
        return None
    need = k.stream_bytes(run["conf"], touched / in_window, 1.0)
    return 100.0 * need / (s / ticks * run["peaks"]["hbm_bytes_s"])
