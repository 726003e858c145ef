"""Mean time of one update's ``write_batch`` (the online store's merge path),
from the benchmark's span around each call in the window."""


def read(run):
    spans = run.spans.get("store.update")
    return 1e3 * sum(spans) / len(spans) if spans else None
