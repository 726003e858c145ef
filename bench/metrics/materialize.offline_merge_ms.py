"""Mean time of one offline-store merge: ``fs.offline.merge``, the whole
``OfflineStore.merge_with_stats`` call (full-key dedup, chunk append and the
rebuild of each touched shard's sorted index)."""

from bench.program_spans import mean_ms


def read(run):
    return mean_ms("fs.offline.merge")
