"""Flash attention's share of the compute roofline: the FLOPs that
forward and backward need for the steps in the trace, a chip's share of
them, over the kernels' time on a chip and the peak in bfloat16. Under
remat the forward kernel runs twice; the second run is time, not need."""

from chipbench import common
from chipbench.layer_metrics import _programs


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    k = common.load_named("kernels", "flash", run["root"])
    seconds, _ = _programs.kernel_total(run, k.TRACE_PROGRAM, k.TRACE_OPERANDS)
    _, steps = _programs.total(run, r"_step")
    if not seconds or not steps:
        return None
    need = steps * k.step_flops(run["dims"], run["batch"], run["seq"])
    return 100.0 * need / run["chips"] / (seconds * run["peaks"]["bf16_flops"])
