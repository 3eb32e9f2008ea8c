"""Share of the traced window in which no operation ran on the chip, in
a graph cell."""
from bench import readers


def read(run):
    return readers.idle_share(run)
