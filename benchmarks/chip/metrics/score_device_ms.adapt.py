"""Device time (ms) of the scoring kernel's events per adaptation round."""
from chip import readers


def read(run):
    return readers.kernel_ms_per_round(run)
