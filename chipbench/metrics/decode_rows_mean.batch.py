"""Scheduler: mean rows of a decode step, over the window's decode steps."""
import numpy as np


def read(run):
    rows = [len(t["decode"]) for t in run["ticks"] if t["decode"]]
    return float(np.mean(rows)) if rows else None
