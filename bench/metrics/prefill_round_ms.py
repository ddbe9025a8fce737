"""Engine prefill: host time of ``prefill_round`` (ending once the pools
are ready) per call, over the traced stretch."""


def read(run):
    s = run.spans("prefill_round")
    return sum(e - b for b, e in s) / len(s) * 1e3 if s else None
