"""Mean time a GET's ticket waited in the serving front's queue before its
dispatch began: the sum of ``wait_ms_sum`` over the window's
``fs.serving.dispatch`` spans over the sum of their ``tickets``."""

from bench.program_spans import spans


def read(run):
    found = spans("fs.serving.dispatch")
    if not found:
        return None
    tickets = sum(s.attrs.get("tickets", 0) for s in found)
    waited = sum(s.attrs.get("wait_ms_sum", 0.0) for s in found)
    return waited / tickets if tickets else None
