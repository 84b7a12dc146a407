"""Engine tick: mean host milliseconds of the commit a step after the
host has the sampled tokens (outputs appended, retirement;
``TickSpan.commit_us``), over the window's steps."""
import program_trace


def read(run):
    return program_trace.phase_ms(run, "commit")
