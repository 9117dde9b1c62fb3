"""Device time a ``jit_admit`` call spends in the gated short
convolutions' own part over the prompt windows (the gates and the sum of
three shifted copies, every convolution layer of every trip), from the
operations whose ``op_name`` holds ``tk_gconv_seq``."""

from chipbench.layer_metrics import _gconv, _programs


def read(run):
    total = _gconv.seconds(run, r"admit", _gconv.SEQ)
    calls = _programs.total(run, r"admit")[1] if total else 0
    return 1e3 * total / calls if calls else None
