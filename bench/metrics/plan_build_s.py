"""Seconds of set-up spent building the program's plans for the cell's
matrices (host clock around the plan calls)."""


def read(run):
    return run.setup_phases.get("plan_build")
