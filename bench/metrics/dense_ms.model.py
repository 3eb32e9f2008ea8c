"""Device milliseconds a call spends in the model step outside SpMM
(embedding, attention, norms, the MLP's own activation and residual,
unembedding): device trace joined with the program's scopes
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.DENSE)
