"""Scheduler: mean host milliseconds of ``Scheduler.tick`` a step
(``TickSpan.plan_us``, on the engine's clock), over the window's steps."""
import program_trace


def read(run):
    return program_trace.phase_ms(run, "plan")
