"""Mean time of the online store's key scan per kernel lookup:
``fs.store.lookup.scan``, from the routed queries' upload through the host
copy of the slot matrix (the ``lookup`` program and its Pallas kernel)."""

from bench.program_spans import mean_ms


def read(run):
    return mean_ms("fs.store.lookup.scan")
