"""The client: the 99th percentile of the gaps between consecutive tokens
of a request, WAN included, as the end-to-end ``itl_p99_ms`` reduces them
(``bench.stats``), over the gaps that begin and end in the traced
stretch.  None where no gap lies there."""
import numpy as np

from bench import stats


def read(run):
    if run.wan is None:
        return None
    lo, hi = run.window
    gaps = []
    for r in run.loop.records:
        t = np.asarray(r.token_times, float)
        inside = (t[:-1] >= lo) & (t[1:] <= hi)
        if inside.any():
            tok = stats.wan_delays(run.wan, r.route, r.prompt_len)[1]
            gaps.append(np.diff(t)[inside] + tok)
    v = stats.percentile(np.concatenate(gaps), 99) if gaps else None
    return None if v is None else v * 1e3
