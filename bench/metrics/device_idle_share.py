"""Device: the share of the traced stretch in which no program ran on the
chip, averaged over the chips used, leaving out the gaps in which an open
loop had nothing in flight and waited for its next arrival (they follow
the offered load, not the system)."""


def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    waited = dict(t.idle_by_host_span(k=None)).get("generator_idle", 0.0)
    if t.window_s - waited <= 0:
        return None
    return (1.0 - t.busy_s() / (t.window_s - waited)) * 100.0
