"""The trace reduction on a small synthetic trace, and the roofline byte
counts the metric readers use."""

from __future__ import annotations

import pytest

from bench import harness, trace_reduce

MS = 1_000_000  # nanoseconds


def _trace():
    """A 10 ms window: a lookup program (1-3 ms) and a gather program
    (5-6 ms) on the device; the host submits (0-4 ms), pumps (4-8 ms) and
    waits (8-10 ms)."""
    return {
        "devices": {
            0: {
                "modules": [
                    ("jit_lookup(123)", 1 * MS, 3 * MS),
                    ("jit_gather_rows(456)", 5 * MS, 6 * MS),
                    ("jit_lookup(123)", 12 * MS, 13 * MS),  # after the window
                ],
                "ops": [
                    ("%lookup_kernel_call.1 = s32[16,128,1] custom-call(...)",
                     1 * MS, 2 * MS + MS // 2),
                    ("%copy.2 = f32[16,262144,250] copy(...)", 5 * MS, 6 * MS),
                ],
            },
            1: {"modules": [("jit_other(1)", 0, 10 * MS)], "ops": []},
        },
        "host": [
            ("bench.window", 0, 10 * MS),
            ("bench.kv.submit", 0, 4 * MS),
            ("bench.kv.pump", 4 * MS, 8 * MS),
            ("bench.store.update", 4 * MS + MS // 2, 4 * MS + MS // 2 + MS),
            ("bench.kv.wait", 8 * MS, 10 * MS),
        ],
    }


def test_reduce_busy_modules_ops_and_gaps():
    r = trace_reduce.reduce(_trace(), chips=1)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.003)  # 1-3 ms and 5-6 ms
    assert r["module_s"] == pytest.approx({"lookup": 0.002, "gather_rows": 0.001})
    assert r["top_ops"] == [
        ["lookup_kernel_call", pytest.approx(0.0015)],
        ["copy", pytest.approx(0.001)],
    ]
    # idle: 0-1 (submit), 3-5 (midpoint 4 ms: pump), 6-10 (midpoint 8: wait)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"kv.submit": 0.001, "kv.pump": 0.002, "kv.wait": 0.004}
    )
    assert r["longest"]["programs"] == [
        ["lookup", pytest.approx(0.002)], ["gather_rows", pytest.approx(0.001)]]
    assert r["longest"]["idle_gaps"][0] == ["kv.wait", pytest.approx(0.004)]


def test_reduce_averages_over_chips():
    r = trace_reduce.reduce(_trace(), chips=2)
    assert r["busy_s"] == pytest.approx((0.003 + 0.010) / 2)


@pytest.mark.parametrize(
    "event, name",
    [
        ("jit_gather_rows(18171889082091938122)", "gather_rows"),
        ("jit_pit_search(1)", "pit_search"),
        ("rolling_sum", "rolling_sum"),
    ],
)
def test_module_name(event, name):
    assert trace_reduce.module_name(event) == name


@pytest.mark.parametrize(
    "event, name",
    [
        ("%lookup_kernel_call.1 = s32[16,128,1]{2,1,0} custom-call(...)",
         "lookup_kernel_call"),
        ("%copy-start.2 = (s32[16,262144]{1,0}, ...) copy-start(...)", "copy-start"),
        ("%fusion = f32[2048,250]{1,0} fusion(...)", "fusion"),
        ("%reduce.8", "reduce"),
    ],
)
def test_op_name(event, name):
    assert trace_reduce.op_name(event) == name


def _metric(name):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py")


def test_roofline_byte_counts():
    get = _metric("get_kernels_roofline")
    assert get.needed_bytes(4096, 250) == 4096 * (8 + 1000 + 8)
    assert get.needed_bytes(1, 8) == 8 + 32 + 8


class _Run:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_roofline_share_from_trace():
    get = _metric("get_kernels_roofline")
    run = _Run(
        device_trace={"module_s": {"lookup": 0.002, "gather_rows": 0.002}},
        counters={"front.store_keys": 1000},
        config={"features": 250},
        peaks={"hbm_bytes_per_s": 819e9},
    )
    least = 1000 * 1016 / 819e9
    assert get.read(run) == pytest.approx(100 * least / 0.004)
    run.device_trace = {"module_s": {}}
    assert get.read(run) is None  # nothing to read: no metric, never 0
    # one program missing (renamed or fused): its time would be left out
    run.device_trace = {"module_s": {"lookup": 0.002}}
    assert get.read(run) is None
    run.device_trace = {"module_s": {"gather_rows": 0.002}}
    assert get.read(run) is None


def test_idle_share():
    idle = _metric("device_idle.get")
    run = _Run(device_trace={"busy_s": 2.5, "window_s": 10.0})
    assert idle.read(run) == pytest.approx(75.0)
