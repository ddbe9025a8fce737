"""Engine round: host time of ``decode_round`` (which ends in its one host
sync) per round, over the traced stretch."""


def read(run):
    s = run.spans("decode_round")
    return sum(e - b for b, e in s) / len(s) * 1e3 if s else None
