"""Engine prefill: the share of the token-layers the prefill step computed
that a prompt needed, over the program's ``prefill.hop`` spans in the
traced stretch (``tok_layers_useful``, each member's chunk times its
layers, over ``tok_layers_computed``, every pool row at the padded bucket
through every hosted layer)."""
from bench.program_spans import count_ratio


def read(run):
    return count_ratio(run, "prefill.hop", "tok_layers_useful",
                       "tok_layers_computed")
