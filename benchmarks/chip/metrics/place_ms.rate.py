"""Mean ``place`` span (ms): delta applied, arrivals placed, tracker updated."""
from chip import readers


def read(run):
    return readers.mean_span_ms(run, "place")
