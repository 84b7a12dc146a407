"""Engine tick: device-idle milliseconds inside each engine step span, per
step, over the traced part of the window."""
import readers


def read(run):
    return readers.host_gap_ms_per_tick(run)
