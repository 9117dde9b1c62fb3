"""The sliding-window flash forward's share of the compute roofline: the
FLOPs the window needs (``chipbench/kernels/flash_window.py``) for the
calls of ``tk_flash_fwd_win`` in the traced admissions, over the kernel's
device time and the peak in bfloat16."""

from chipbench import common


def read(run):
    tr = run.get("trace")
    window = run["conf"].get("sliding_window")
    if not tr or not window:
        return None
    k = common.load_named("kernels", "flash_window", run["root"])
    need = seconds = 0.0
    for key, t in tr["kernels"].items():
        shape = k.operands(t["text"])
        if k.NAME in key and shape and t["total_s"]:
            need += t["count"] * k.call_flops(shape[0], shape[1], shape[2], window)
            seconds += t["total_s"]
    if not seconds:
        return None
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops"])
