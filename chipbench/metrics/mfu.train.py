"""Model step: work-count FLOPs of the traced training steps (forward and
backward, attention over each query's window only; recomputation does not
count) over the traced time times the chips' peak FLOP/s."""
import readers


def read(run):
    return readers.train_mfu(run)
