"""Device time of the gated short convolution's own part of one layer of
one tick (the gates, the three taps over the tail, the tail's roll: what
follows the in-projection and is not a matrix product), from the
operations whose ``op_name`` holds ``tk_gconv_step``. An XLA fusion whose
edges the compiler chooses: no roofline is reckoned for it."""

from chipbench.layer_metrics import _gconv, _latent_ops


def read(run):
    total = _gconv.seconds(run, r"tick", _gconv.STEP)
    calls = _latent_ops.ticks_traced(run) * _gconv.conv_layers(run) if total else 0
    return 1e6 * total / calls if calls else None
