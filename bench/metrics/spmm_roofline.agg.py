"""SpMM kernel's share of its roofline in a graph cell, reduced as
``spmm_roofline.model`` is."""
from bench import readers


def read(run):
    return readers.spmm_roofline(run)
