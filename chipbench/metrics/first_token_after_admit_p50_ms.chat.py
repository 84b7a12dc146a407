"""Scheduler: median of first admission to first committed output token,
on the scheduler's clock (``Request.t_first - t_admit``), over requests
due in the window (in a traced run, before the trace started)."""
import program_trace
import readers


def read(run):
    v = readers.percentile(
        program_trace.stamp_gaps_s(run, "t_admit", "t_first"), 50)
    return None if v is None else 1e3 * v
