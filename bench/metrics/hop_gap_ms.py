"""Between hops: for each decode round begun in the traced stretch, the
time from the first round-step run (``jit_hop``) to the last in which no
round step runs on any chip, averaged over the rounds of two hops or
more.  A round's hops are the ``jit_hop`` runs, on whichever chips, that
begin inside its ``decode_round`` span; the hops of a round run one after
another, so this is the sum of the gaps between the end of one hop's run
and the start of the next.  On one chip it is the host's work between
hops; across chips the move of the hidden rows to the next chip adds to
it.  None where no round has two hops."""
import numpy as np

from bench.trace import module_base


def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    hops = sorted((s, e) for d in t.devices for n, s, e in t.modules[d]
                  if module_base(n) == "jit_hop")
    starts = np.asarray([s for s, _ in hops])
    lo = run.window[0]
    gaps = []
    for a, b in run.spans("decode_round"):
        i, j = np.searchsorted(starts, [a - lo, b - lo])
        if j - i < 2:
            continue
        gap, end = 0.0, hops[i][1]
        for s, e in hops[i + 1:j]:
            gap += max(0.0, s - end)
            end = max(end, e)
        gaps.append(gap)
    return float(np.mean(gaps)) * 1e3 if gaps else None
