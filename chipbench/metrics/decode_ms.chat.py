"""Model step: device milliseconds per call of the jitted decode step."""
import numpy as np

import readers


def read(run):
    dev = readers.device_seconds(run, "decode")
    return 1e3 * float(np.mean(dev)) if dev else None
