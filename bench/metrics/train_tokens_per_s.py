"""Training tokens of every iteration completed in the window of one long
trial, over the time from the window's opening to the last of those results
(host clock): the same count as ``sweep_tokens_per_s``, in the cells whose
window holds steps only."""
from bench.metrics.sweep_tokens_per_s import read  # noqa: F401
