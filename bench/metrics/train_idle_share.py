"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / window), averaged over
the chips used.  Layer: device.  Moves train_tokens_per_s."""


def read(ctx):
    return 100.0 * ctx.reduction.idle_share
