"""Device: 1 - union of the device's busy intervals over the traced window."""
import readers


def read(run):
    return readers.idle_share(run)
