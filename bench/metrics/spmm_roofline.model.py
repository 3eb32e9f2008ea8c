"""SpMM kernels' share of their roofline in a model cell: the least time
of every SpMM call the traced window made (operations over the bf16 peak
or compulsory bytes over HBM bandwidth, whichever is larger; see
``bench/work.py``) over the summed device time of the SpMM kernel events
in the trace."""
from bench import readers


def read(run):
    return readers.spmm_roofline(run)
