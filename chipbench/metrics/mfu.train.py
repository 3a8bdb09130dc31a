"""The whole SeedFlood step's share of the chips' bf16 peak: model FLOPs
of the ± perturbed forwards and the fold (``costs.train_step_flops``) of
every step in the traced window, over the window's time × chips × peak."""


def read(m):
    rec, ctx = m["rec"], m["ctx"]
    if not rec["steps"]:
        return None
    return 100.0 * m["cost"]["step_flops"] * rec["steps"] / (
        rec["elapsed_s"] * ctx.chips * m["peak"]["bf16_flops"])
