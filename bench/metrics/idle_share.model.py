"""Share of the traced window in which no operation ran on the chip
(1 - union of device-operation intervals / window), in a model cell."""
from bench import readers


def read(run):
    return readers.idle_share(run)
