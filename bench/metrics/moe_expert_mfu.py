"""Model FLOP/s utilization of the expert layer's grouped matmuls in the
traced window: 3 x the launcher's counter ``train.moe_expert_flops`` (the
forward FLOPs the dropless layer owes for the assignments to its held
experts, 6 d ff each, which the program counts itself), for forward and
backward, over the window seconds and the chip's bf16 peak.  The counters
cover the set-up period and the window; the count is scaled to the window
by its share of the rounds (the runner's ``rounds`` over the counter
``train.rounds``).  Layer: expert layer.  Moves train_tokens_per_s.  None
where the program keeps no such counter."""


def read(ctx):
    try:
        from repro.obs.metrics import counter_totals
    except ImportError:
        return None
    totals = counter_totals()
    flops = totals.get("train.moe_expert_flops")
    rounds = totals.get("train.rounds")
    if not flops or not rounds or not ctx.counts.get("rounds"):
        return None
    window_flops = 3.0 * flops * ctx.counts["rounds"] / rounds
    return (100.0 * window_flops / ctx.reduction.window_s
            / ctx.peak["bf16_flops_per_s"])
