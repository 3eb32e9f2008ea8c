"""Share of the traced window in which no operation ran on a chip,
averaged over the cell's chips (1 - busy / window), in a cell whose
model is spread over them."""
from bench import readers


def read(run):
    return readers.idle_share(run)
