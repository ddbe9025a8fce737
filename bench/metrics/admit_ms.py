"""Routing: host time of ``OnlineBPRR.admit`` plus ``create_session`` per
arrival, over the traced stretch."""


def read(run):
    s = run.spans("admit")
    return sum(e - b for b, e in s) / len(s) * 1e3 if s else None
