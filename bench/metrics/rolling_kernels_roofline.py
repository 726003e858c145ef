"""The rolling aggregates' device work against its roofline: the bytes the
window's jobs need at the chip's peak HBM bandwidth, over the device time of
the rolling programs (``rolling_sum`` with its Pallas kernel,
``rolling_extreme``, and ``rolling_sum_xla`` where a span was too deep for
the kernel).

It counts the work the jobs need, not the work the DSL does: each source
row that some kept row's window holds, read once (timestamp, entity and the
source columns), and every feature of the rows written, once.  The first is
the generator's count from the data (``materialize.rows_needed``: the rows
of each job's lookback whose customer has a sale in the job's hour), the
second the ``rows_out`` of the ``fs.materialize.job`` spans.  So a DSL that
aggregates the whole lookback is read against the same yardstick."""

from bench.program_spans import spans

PROGRAMS = ("rolling_sum", "rolling_extreme")


def needed_bytes(rows_in: float, rows_out: float, sources: int, features: int) -> float:
    """Jobs that need ``rows_in`` source rows (an 8-byte timestamp, an
    8-byte entity key and ``sources`` float32 columns each) and write
    ``rows_out`` rows of ``features`` float32 features."""
    return rows_in * (8 + 8 + 4 * sources) + rows_out * 4 * features


def read(run):
    jobs = spans("fs.materialize.job")
    rows_in = run.counters.get("materialize.rows_needed")
    module_s = (run.device_trace or {}).get("module_s", {})
    ran = list(PROGRAMS)
    if run.counters.get("materialize.rolling_fallbacks"):
        ran.append("rolling_sum_xla")
    # a program missing from the trace (renamed or fused) would leave its
    # time out and overstate the share: then there is nothing to read
    if not jobs or not rows_in or not all(module_s.get(m) for m in ran):
        return None
    features = run.config["features"]
    need = needed_bytes(
        rows_in,
        sum(j.attrs["rows_out"] for j in jobs),
        len({f["source"] for f in features}),
        len(features),
    )
    device_s = sum(module_s.get(m, 0.0) for m in ("rolling_sum_xla", *PROGRAMS))
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / device_s
