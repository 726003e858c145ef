"""Mean time of one online-store merge: ``fs.store.merge``, the whole
``OnlineStore.merge`` or ``merge_reduced`` call with its listeners, without
the caller's building of the batch."""

from bench.program_spans import mean_ms


def read(run):
    return mean_ms("fs.store.merge")
