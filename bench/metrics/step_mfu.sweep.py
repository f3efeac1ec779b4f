"""``step_mfu`` in the sweep cells, where it moves ``sweep_tokens_per_s``:
the model FLOPs of the train steps the profiler saw over their device time
times the chip's bf16 peak, in percent."""
from bench.metrics.step_mfu import read  # noqa: F401
