"""Pooled prefill step: device time of the prefill programs (``jit_step``)
per prefill round, from the traced stretch of the window."""


def read(run):
    n = len(run.spans("prefill_round"))
    if run.trace is None or not n or not run.trace.module_count("jit_step"):
        return None
    return run.trace.module_s("jit_step") / n * 1e3
