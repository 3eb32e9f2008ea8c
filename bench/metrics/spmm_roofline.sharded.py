"""SpMM kernels' share of their roofline in a cell whose matrices are
row-sharded over its chips: the least time of every global SpMM call the
traced window made, on all the cell's chips together, over the SpMM
kernel events' device time a chip (``readers.spmm_roofline``)."""
from bench import readers


def read(run):
    return readers.spmm_roofline(run)
