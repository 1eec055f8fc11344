"""Share of the client-local steps the round engine computed that belong
to participating clients: the launcher's counters
``train.client_steps_useful`` / ``train.client_steps_computed``
(`repro.obs.counter_totals`), in percent.  The totals cover the process's
rounds, set-up period and window alike; both are whole schedule periods,
so the share is the window's.  Layer: round engine.  Moves
train_tokens_per_s: work computed for non-participants is discarded.
None where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.obs.metrics import counter_totals
    except ImportError:
        return None
    totals = counter_totals()
    computed = totals.get("train.client_steps_computed")
    if not computed:
        return None
    return 100.0 * totals.get("train.client_steps_useful", 0) / computed
