"""Model step, whole window: FLOPs of every prefill and decode step by the
work count, over the traced window times the peak FLOP/s."""
import readers


def read(run):
    return readers.mfu(run)
