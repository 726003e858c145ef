"""Keys the serving front coalesced into each store dispatch in the window
(``coalesced_keys / dispatches`` of its counters)."""


def read(run):
    dispatches = run.counters.get("front.dispatches", 0)
    return run.counters["front.coalesced_keys"] / dispatches if dispatches else None
