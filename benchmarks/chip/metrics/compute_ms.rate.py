"""Mean ``compute`` span (ms): one PageRank superstep and its message count."""
from chip import readers


def read(run):
    return readers.mean_span_ms(run, "compute")
