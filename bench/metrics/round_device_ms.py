"""Device-busy milliseconds per federated round in the traced window (the
union of operation intervals / rounds).  Layer: round engine.  Moves
train_tokens_per_s; it moves even where host gaps hide a change end to
end."""


def read(ctx):
    rounds = ctx.counts.get("rounds")
    if not rounds:
        return None
    return 1e3 * ctx.reduction.busy_s / rounds
