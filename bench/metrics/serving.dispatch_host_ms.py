"""Mean host time of one serving-front dispatch outside the store: the self
time of ``fs.serving.dispatch`` (dedup, cache re-probe and refill, scatter
to the tickets), which leaves out its ``fs.store.lookup``."""

from bench.program_spans import mean_ms


def read(run):
    return mean_ms("fs.serving.dispatch", own=True)
