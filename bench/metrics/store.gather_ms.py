"""Mean time of the online store's row gather per kernel lookup:
``fs.store.lookup.gather``, from the ``gather_rows`` call through the host
copies of the rows and their creation timestamps."""

from bench.program_spans import mean_ms


def read(run):
    return mean_ms("fs.store.lookup.gather")
