"""Least time of a scoring pass (cost.py, peaks.json) over the kernel's device time per round, in %."""
from chip import readers


def read(run):
    return readers.roofline_pct(run)
