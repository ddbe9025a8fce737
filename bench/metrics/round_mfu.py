"""The whole decode round's share of the chip's peak: model FLOPs of the
tokens decoded in the traced stretch's rounds (every block, the attention
over each token's context, the head) over the rounds' host time times the
peak bf16 rate of the chips used; the FLOPs are counted by
``token_model_flops`` of the configuration's reference module."""


def read(run):
    if run.peaks is None:
        return None
    spans = run.spans("decode_round")
    rounds = run.rounds()
    if not spans or not rounds:
        return None
    flops = sum(run.arch.token_model_flops(run.model, p) for pos in rounds
                for p in pos)
    chips = run.deployment.get("chips", 1)
    wall = sum(e - b for b, e in spans)
    return flops / (wall * run.peaks["flops_bf16"] * chips) * 100.0
