"""Model FLOP/s utilization of the traced window: the forward and backward
FLOPs of the participating clients' local steps (`flops/<family>.py`, no
recomputation, no non-participant's work) / window seconds / the chip's
bf16 peak.  Layer: model step.  Moves train_tokens_per_s."""


def read(ctx):
    c = ctx.counts
    if not c.get("useful_flops"):
        return None
    return (100.0 * c["useful_flops"] / ctx.reduction.window_s
            / ctx.peak["bf16_flops_per_s"])
