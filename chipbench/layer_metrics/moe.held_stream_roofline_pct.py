"""The held experts' matmuls' share of the memory roofline in a decode
tick: the bytes of the held experts the program counted as touched (over
the window, a tick), over those matmuls' time a tick in the trace and the
chip's peak bytes a second. The same work whatever implements it: the
operations are those of the tick that read the held weights, told by
operand shape (``chipbench/kernels/moe_share.py``). A form that streams
untouched held experts too spends time on bytes that are not needed and
not counted."""

from chipbench.layer_metrics import _latent_ops as L


def read(run):
    if not run.get("trace") or "experts_held" not in run["conf"]["deployment"]:
        return None
    k = L.kernels(run, "moe_share")
    s = L.seconds(run, k.operand_pattern(run["conf"]))
    touched = L.section_delta(run, "expert_layer", "moe_experts_touched")
    ticks, in_window = (L.ticks_traced(run) if s else 0), L.ticks_in_window(run)
    if not ticks or not touched or not in_window:
        return None
    need = k.stream_bytes(run["conf"], touched / in_window)
    return 100.0 * need / (s / ticks * run["peaks"]["hbm_bytes_s"])
