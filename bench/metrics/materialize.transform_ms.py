"""Mean time of one materialization job's transform: ``fs.materialize.transform``,
the DSL's sort of the lookback rows, its window starts and tie ends, the
rolling programs and their copies back to the host."""

from bench.program_spans import mean_ms


def read(run):
    return mean_ms("fs.materialize.transform")
