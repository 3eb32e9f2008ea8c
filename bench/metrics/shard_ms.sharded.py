"""Device milliseconds a call spends, on a chip, in the steps a sharded
SpMM adds around its shards: each shard's values gathered out of the
global value vector every call (scope ``shard_vals``) and the output
assembled from the row blocks, the collective included (scope
``shard_concat``).  Device trace joined with the program's scopes
(``bench/scopes.py``), averaged over the cell's chips; None for a
program that has neither scope."""
from bench import scopes

SHARD = ("shard_vals", "shard_concat")


def read(run):
    try:
        from repro.obs import SCOPES
    except ImportError:
        return None
    if not all(s in SCOPES for s in SHARD):
        return None
    return scopes.ms_per_call(run, SHARD)
