"""Share (%) of the profiled supersteps in which the chip ran nothing."""
from chip import readers


def read(run):
    return readers.idle_pct(run)
