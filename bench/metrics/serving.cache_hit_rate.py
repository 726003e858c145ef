"""Share of the window's requested keys that the serving front's hot-key
cache served fresh (``ServingFront`` counters, as ``stats()`` reports them)."""


def read(run):
    hits = run.counters.get("front.cache_hits", 0)
    total = hits + run.counters.get("front.cache_misses", 0)
    return 100.0 * hits / total if total else None
