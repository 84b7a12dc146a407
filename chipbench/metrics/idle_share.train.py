"""Device: share of the traced window in which no operation ran, averaged
over the devices."""
import readers


def read(run):
    return readers.idle_share(run)
