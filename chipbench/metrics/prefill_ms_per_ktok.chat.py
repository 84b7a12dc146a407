"""Model step: device milliseconds of the jitted prefill steps per 1000
prompt tokens they computed, over the traced part of the window."""
import readers


def read(run):
    dev = readers.device_seconds(run, "prefill")
    toks = sum(t["prefill"][1] for t in readers.traced_ticks(run)
               if t["prefill"] is not None)
    return 1e6 * sum(dev) / toks if dev and toks else None
