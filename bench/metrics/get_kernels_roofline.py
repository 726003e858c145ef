"""The GET's device work against its roofline: the bytes a lookup must move
(each key in, its feature row and creation timestamp out) at the chip's peak
HBM bandwidth, over the device time of the programs the GET runs
(``lookup``, the key scan with its Pallas kernel, and ``gather_rows``).

It counts the work the operation needs, not the work the current kernels
do (a scan of every slot), so an index search is read against the same
yardstick."""


def needed_bytes(keys: float, features: int) -> float:
    """A GET of ``keys`` keys: an 8-byte key in, a ``features``-wide float32
    row and an 8-byte creation timestamp out, per key."""
    return keys * (8 + 4 * features + 8)


PROGRAMS = ("lookup", "gather_rows")


def read(run):
    module_s = (run.device_trace or {}).get("module_s", {})
    keys = run.counters.get("front.store_keys", 0)
    # a program missing from the trace (renamed or fused) would leave its
    # time out and overstate the share: then there is nothing to read
    if not keys or not all(module_s.get(m) for m in PROGRAMS):
        return None
    device_s = sum(module_s[m] for m in PROGRAMS)
    least_s = needed_bytes(keys, int(run.config["features"])) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
