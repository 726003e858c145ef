"""99th percentile of the window's update latency, each update timed from
its due time to the return of its ``write_batch``: the merge path's tail,
read per layer beside the end-to-end median."""


def read(run):
    return run.counters.get("window.update_p99_ms")
