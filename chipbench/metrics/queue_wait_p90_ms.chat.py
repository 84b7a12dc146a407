"""Scheduler: 90th percentile of due time to admission (the host time of
the tick ``SeqState.admitted_at`` names), over requests due in the window
(in a traced run, before the trace started)."""
import readers


def read(run):
    v = readers.percentile(readers.queue_wait_s(run), 90)
    return None if v is None else 1e3 * v
