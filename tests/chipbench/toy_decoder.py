"""The toy configurations' model module: the dense decoder with flash
attention forced (off the TPU the program would choose the XLA path). A
configuration names the module that builds its model, so the toy brings
one of its own instead of a switch in the benchmark."""

from chipbench.models import dense_decoder as _dense
from chipbench.models.dense_decoder import *  # noqa: F401,F403


def program_config(conf, max_seq_len, **extra):
    extra.setdefault("attn_impl", "flash")
    return _dense.program_config(conf, max_seq_len, **extra)
