"""Shared by the readers of whole programs' device time."""

import re


def total(run, pattern: str):
    """Seconds and runs of the jitted programs whose name matches."""
    tr = run["trace"]
    if not tr:
        return 0.0, 0
    seconds = count = 0.0
    for name, t in tr["programs"].items():
        if re.search(pattern, name):
            seconds += t["total_s"]
            count += t["count"]
    return seconds, count


def kernel_total(run, program: str, operands: str):
    """Seconds and runs of the Pallas kernels inside the programs whose
    name matches ``program`` and whose operation text (operand shapes and
    types, as the trace prints them) matches ``operands``."""
    tr = run["trace"]
    if not tr:
        return 0.0, 0
    seconds = count = 0.0
    for t in tr["kernels"].values():
        if re.search(program, t["program"]) and re.search(operands, t["text"]):
            seconds += t["total_s"]
            count += t["count"]
    return seconds, count


def tick_ms(run):
    """Device time of one decode tick: the tick program's time in the
    trace over its runs and the ticks chained in each."""
    seconds, count = total(run, r"tick")
    if not count:
        return None
    return 1e3 * seconds / (count * run["conf"]["deployment"]["ticks_per_sync"])
