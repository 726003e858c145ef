"""Share of the traced window in which no operation ran on the device, in
the GET cells: 1 - busy / window, from the profiler's trace (averaged over
the chips used)."""


def read(run):
    t = run.device_trace
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
