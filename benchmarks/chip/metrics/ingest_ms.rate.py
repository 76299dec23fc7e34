"""Mean ``ingest`` span (ms) of the window's supersteps: the host batch build."""
from chip import readers


def read(run):
    return readers.mean_span_ms(run, "ingest")
