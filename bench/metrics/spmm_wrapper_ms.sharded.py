"""Device milliseconds a call spends, on a chip, in the SpMM wrappers
around the kernels of its row shards (values gathered into slot order, B
laid out and widened, padding, the output laid back out): device trace
joined with the program's scopes (``bench/scopes.py``), averaged over
the cell's chips."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.WRAPPER)
