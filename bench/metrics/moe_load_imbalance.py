"""How unevenly the dropless expert layer's held experts are loaded: the
launcher's counters ``train.moe_load_max`` / ``train.moe_load_mean``, the
busiest held expert's rows over the mean rows a held expert, each summed
over layers, local steps and participants (set-up period and window
alike).  1.0 means even.  With experts on different chips the busiest one
sets the pace of a dropless layer.  Layer: expert layer.  Moves
train_tokens_per_s.  None where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.obs.metrics import counter_totals
    except ImportError:
        return None
    totals = counter_totals()
    mean = totals.get("train.moe_load_mean")
    if not mean:
        return None
    return totals.get("train.moe_load_max", 0.0) / mean
