"""Mean time of a merge's slot resolution: ``fs.store.merge.resolve``, the
index find and, when the host mirror is stale, the device gather of the old
timestamps and its copy to the host."""

from bench.program_spans import mean_ms


def read(run):
    return mean_ms("fs.store.merge.resolve")
