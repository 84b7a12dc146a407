"""Engine tick: mean host milliseconds of the dispatch a step (slot
resets, block tables, the enqueue of copies, prefill and decode;
``TickSpan.dispatch_us``), over the window's steps."""
import program_trace


def read(run):
    return program_trace.phase_ms(run, "dispatch")
