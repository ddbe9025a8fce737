"""Engine prefill: device idle per prefill round, from the program's own
spans: the idle gaps of the traced stretch whose middle falls inside a
program ``prefill_round`` span, averaged over the chips, over the number
of those spans in the stretch (``bench.program_spans``)."""
from bench.program_spans import idle_under


def read(run):
    got = idle_under(run, "prefill_round")
    return None if got is None else got[0] / got[1] * 1e3
