"""Device milliseconds a call spends in the SpMM wrappers around the
kernels (values gathered into slot order, B laid out and widened,
padding, the output laid back out), in a model cell: device trace joined
with the program's scopes (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.WRAPPER)
