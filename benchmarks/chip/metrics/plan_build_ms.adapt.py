"""Host time (ms) of the scoring plan's build (``plan.build`` program span:
the BSR pack and its upload) per profiled adaptation."""
from chip import readers


def read(run):
    return readers.mean_span_ms(run, "plan.build")
