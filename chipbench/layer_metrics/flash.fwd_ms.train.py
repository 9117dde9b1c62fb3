"""Device time a training step spends, on a chip, in the flash forward
kernel (under remat it runs twice a layer), found by the kernel's own
name."""

from chipbench.layer_metrics import _named, _programs


def read(run):
    seconds, _ = _named.kernel_total(run, "tk_flash_fwd", r"_step")
    _, steps = _programs.total(run, r"_step")
    return 1e3 * seconds / steps if seconds and steps else None
