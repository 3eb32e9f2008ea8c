"""Useful (unpadded) tokens completed per second: every token of every
call or request completed in the window, over the time from the window's
start to the last completion (host clock)."""


def read(run):
    w = run.window
    if not w.tokens:
        return None
    return w.tokens / w.seconds
