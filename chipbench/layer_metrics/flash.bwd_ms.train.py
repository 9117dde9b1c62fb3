"""Device time a training step spends, on a chip, in the two flash
backward kernels (dq, and dk with dv), found by the kernels' own names."""

from chipbench.layer_metrics import _named, _programs


def read(run):
    seconds = sum(
        _named.kernel_total(run, name, r"_step")[0]
        for name in ("tk_flash_bwd_dq", "tk_flash_bwd_dkv")
    )
    _, steps = _programs.total(run, r"_step")
    return 1e3 * seconds / steps if seconds and steps else None
