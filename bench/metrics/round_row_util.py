"""Engine round: the share of the row-layers the round step computed that
a session needed, over the program's ``round.hop`` spans in the traced
stretch (``row_layers_useful`` over ``row_layers_computed``: a hop runs
every pool row through every hosted layer and masks the rest)."""
from bench.program_spans import count_ratio


def read(run):
    return count_ratio(run, "round.hop", "row_layers_useful",
                       "row_layers_computed")
