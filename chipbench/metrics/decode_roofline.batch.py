"""Model step: the least time of the decode steps by the work count (the
larger of FLOPs over peak and bytes over peak) over their device time."""
import readers


def read(run):
    return readers.decode_roofline(run)
