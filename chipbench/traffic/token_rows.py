"""Rows of token ids for a training topic, all of one length."""

from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int, frame: dict) -> dict:
    rows = int(params["steps_cap"]) * int(frame["batch"])
    rng = np.random.default_rng([int(seed), 0x70C5])
    return {
        "rows": rng.integers(
            0, frame["vocab"], (rows, int(frame["seq"])), dtype=np.int32
        )
    }
