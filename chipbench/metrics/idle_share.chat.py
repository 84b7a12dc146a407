"""Device: share of the traced window in which no operation ran."""
import readers


def read(run):
    return readers.idle_share(run)
