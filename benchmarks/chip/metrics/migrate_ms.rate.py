"""Mean ``migrate`` span (ms): the interleaved migration rounds on the backend."""
from chip import readers


def read(run):
    return readers.mean_span_ms(run, "migrate")
