"""The whole step's share of the bf16 peak of the cell's chips together,
in a cell whose model is spread over its chips: ``step_mfu``'s reading,
which divides by the chip count times one chip's peak."""
from bench.metrics.step_mfu import read  # noqa: F401
