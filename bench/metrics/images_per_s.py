"""Images whose responses were opened inside the window, per second."""
import readers


def read(run):
    return readers.units_per_s(run, "images")
