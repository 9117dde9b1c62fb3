"""The selected flash forward's share of the compute roofline: the FLOPs
of the causal triangle (``chipbench/kernels/dsa.py`` says why the
triangle) for the calls of ``tk_flash_fwd_sel`` in the traced admissions,
over the kernel's device time and the peak in bfloat16."""

from chipbench import common


def read(run):
    tr = run.get("trace")
    if not tr or "sa_config" not in run["conf"]:
        return None
    k = common.load_named("kernels", "dsa", run["root"])
    # (the queries' shape as the windowed forward's reader finds it)
    operands = common.load_named("kernels", "flash_window", run["root"]).operands
    need = seconds = 0.0
    for key, t in tr["kernels"].items():
        shape = operands(t["text"])
        if k.FLASH_SEL in key and shape and t["total_s"]:
            need += t["count"] * k.flash_sel_flops(*shape)
            seconds += t["total_s"]
    if not seconds:
        return None
    return 100.0 * need / (seconds * run["peaks"]["bf16_flops"])
