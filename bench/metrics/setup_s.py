"""Seconds from the moment the chips are found to the start of the
window: the program imported, weights or graph made from the seed, plan
build, compile (from the persistent cache after a checkout's first run)
and warm-up (host clock).  Python's and the TPU runtime's own start,
before the chips are found, is left out: it is none of the program's
work and spreads by seconds from run to run."""


def read(run):
    return run.setup_s
