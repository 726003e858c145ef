"""99th percentile of the window's GET latency, each GET timed from its due
time in the open-loop schedule to its ticket's completion: the tail that a
host or device stall of a second moves by several times, so it is read per
layer beside the end-to-end median."""


def read(run):
    return run.counters.get("window.get_p99_ms")
