"""Model FLOP utilization of the train step: the model FLOPs of the steps
the profiler saw (forward and backward, no recomputation; ``bench/flops``)
over their device time times the chip's bf16 peak (``bench/peaks.py``), in
percent.  Device time is that of the train-step program's executions in the
trace, summed over chips."""
from bench.peaks import peaks


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["module_n"] or trace["module_s"] <= 0:
        return None
    peak = peaks(ctx["device_kind"])["flops_bf16"]
    flops = trace["module_n"] * ctx["tokens_per_step"] * ctx["flops_per_token"]
    return 100.0 * flops / (trace["module_s"] * peak)
