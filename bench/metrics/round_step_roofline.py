"""The round step's share of its roofline: for each decode round in the
traced stretch, the larger of the FLOPs it needs over the chip's peak and
the bytes it needs over the peak bandwidth (``round_needs`` of the
configuration's reference module), summed, over the device time of the
round-step programs."""


def read(run):
    if run.peaks is None:
        return None
    rounds = run.rounds()
    if run.trace is None or not rounds:
        return None
    dev = run.trace.module_s("jit_hop")
    if dev <= 0:
        return None
    dep, pk, needs = run.deployment, run.peaks, run.arch.round_needs
    need = 0.0
    for pos in rounds:
        fl, by = needs(run.model, pos, dep["servers"], dep["rows"])
        need += max(fl / pk["flops_bf16"], by / pk["hbm_bytes_per_s"])
    return need / dev * 100.0
