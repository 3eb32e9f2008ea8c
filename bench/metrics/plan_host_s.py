"""Host-clock seconds of set-up's plan builds in two steps, summed from
the program's ``plan_build_step_seconds``: ``fingerprint`` (the pattern
copied to the host and hashed) and ``row_ids`` (row ids built on the
host and copied back).  Not pure host work: each step's copy to the host
waits for device work an earlier build left in flight (the structure
builders dispatch asynchronously), so the metric also moves with that
device work and with the compile-cache state that sets how long it
takes.  None for a program without the histogram."""

STEPS = ("fingerprint", "row_ids")


def read(run):
    from repro import obs
    fam = obs.registry.get("plan_build_step_seconds")
    if fam is None:
        return None
    return sum(c.sum for c in fam.children() if c.labels["step"] in STEPS)
