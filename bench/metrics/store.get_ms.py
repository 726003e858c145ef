"""Mean time of one store lookup per dispatch in the window: the serving
front's ``serving/kernel_us`` stage, which spans the whole
``OnlineStore.lookup_encoded`` call up to its blocking copy to the host."""


def read(run):
    us = run.counters.get("front.kernel_us_mean")
    return us / 1e3 if us else None
