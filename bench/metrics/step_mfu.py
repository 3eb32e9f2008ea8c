"""The whole step's share of the chip's bf16 peak: operations the
completed work requires (``bench/work.py``: kept nonzeros, dense
projections, causal attention, unembedding) per second of the traced
window, over the peak."""


def read(run):
    w = run.window
    if run.peak is None or not w.flops:
        return None
    return 100.0 * w.flops / w.seconds / run.peak["bf16_flops_per_s"]
