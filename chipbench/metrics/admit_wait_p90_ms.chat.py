"""Scheduler: 90th percentile of submit to first admission, on the
scheduler's clock (``Request.t_admit - t_submit``), over requests due in
the window (in a traced run, before the trace started)."""
import program_trace
import readers


def read(run):
    v = readers.percentile(
        program_trace.stamp_gaps_s(run, "t_submit", "t_admit"), 90)
    return None if v is None else 1e3 * v
