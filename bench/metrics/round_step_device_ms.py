"""Pooled round step: device time of the round-step programs (``jit_hop``,
one per hop) per decode round, from the traced stretch of the window."""


def read(run):
    n = len(run.rounds())
    if run.trace is None or not n or not run.trace.module_count("jit_hop"):
        return None
    return run.trace.module_s("jit_hop") / n * 1e3
