"""Median of due time to first token, over every request due in the window
(one still waiting at the close counts with its wait so far)."""
import readers


def read(run):
    v = readers.percentile(readers.ttft_s(run), 50)
    return None if v is None else 1e3 * v
