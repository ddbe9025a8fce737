"""An architecture module that exists only for the tests: a cost count
with no formula in common with any configuration's, so that a reader
giving its numbers has taken them from the run's module."""


def round_needs(m, positions, n_hops, width):
    return (m["flops_per_row"] * len(positions),
            m["bytes_per_slot"] * n_hops * width)


def token_model_flops(m, position):
    return m["flops_per_row"] + position
