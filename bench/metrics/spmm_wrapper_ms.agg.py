"""Device milliseconds an aggregation spends in the SpMM wrapper around
the kernel, in a graph cell, reduced as ``spmm_wrapper_ms.model`` is."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.WRAPPER)
