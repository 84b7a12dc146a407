"""Tokens of the window's whole training steps over the time from the
first one's start to the last one's end (each step's loss read on the
host, as a training job logs it)."""
import readers


def read(run):
    return readers.train_tok_s(run)
