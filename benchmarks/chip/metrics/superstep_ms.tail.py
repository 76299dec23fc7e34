"""Mean ``superstep`` span (ms) of the window: what a committed event waits for."""
from chip import readers


def read(run):
    return readers.mean_span_ms(run, "superstep")
