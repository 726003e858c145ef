"""jit'd public wrapper around the rolling-window aggregation kernel.

Handles everything the raw kernel does not: the transposed (F, N) layout
with features padded to 8 sublanes, row padding to block multiples, row
bucketing (``rolling_agg`` pads N on the host to ``row_bucket(N)``, so a
stream of jobs whose row counts differ runs a few compiled programs, not
one per N), span bucketing (the kernel needs a static history depth >= the
maximum window row-span), the VMEM bound on that depth, and the derived
aggregations (count is closed-form; mean = sum / count; min/max use an XLA
doubling formulation — the windowed-sum matmul does not apply to them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rolling_agg.kernel import VMEM_LIMIT_BYTES, rolling_sum_kernel_call

__all__ = [
    "max_hist",
    "rolling_agg",
    "rolling_extreme",
    "rolling_sum",
    "rolling_sum_xla",
    "row_bucket",
    "window_starts",
]

_LANE = 128
_SUBLANE = 8
_DEFAULT_BLOCK = 256
_SCAN_BLOCK = 1024  # rows per block of rolling_sum_xla's two-level scan
# The smallest row bucket: a program over this many float32 rows moves
# 64 KiB a column, so its time is its launch, and calls whose row counts
# wander below it (a materialization job's entities) share one program.
_MIN_ROWS = 16_384
# Doubling levels below which min/max never go: spans up to 127 rows share
# one program (a level costs an elementwise pass; the gathers cost the rest).
_MIN_LEVELS = 7


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def window_starts(
    segment_ids: np.ndarray, timestamps: np.ndarray, window: int
) -> np.ndarray:
    """Host-side window-start computation (rows sorted by (segment, ts)).

    ``starts[i]`` is the first row j of row i's segment with
    ``ts_j > ts_i - window``: where row i's window begins.  Rows tied with
    row i on (segment, ts) share its start.  Where the window ends is the
    caller's: the kernels sum the contiguous span ``[starts[i], i]``, and
    the DSL takes each row's value at the last row of its tie, so that its
    window is every row with ``ts_i - window < ts_j <= ts_i``.  Uses a
    composite monotone key so one global vectorized searchsorted handles
    every segment at once.
    """
    segment_ids = np.asarray(segment_ids, dtype=np.int64)
    timestamps = np.asarray(timestamps, dtype=np.int64)
    if len(segment_ids) == 0:
        return np.zeros((0,), dtype=np.int32)
    t0 = timestamps.min()
    rebased = timestamps - t0
    span = int(rebased.max()) + 2
    key = segment_ids * span + rebased
    if not np.all(np.diff(key) >= 0):
        raise ValueError("rows must be sorted by (segment, timestamp)")
    q = segment_ids * span + np.maximum(rebased - window, -1)
    starts = np.searchsorted(key, q, side="right")
    return starts.astype(np.int32)


def row_bucket(n: int) -> int:
    """Rows the rolling programs are compiled for, given ``n``: ``n``
    rounded up to eight steps per power of two (at least ``_MIN_ROWS``), so
    the padding is under 12.5% of ``n`` and a stream of jobs whose row
    counts wander within a few percent runs one compiled program.  Not the
    online path's ``pow2_bucket``: these programs' work is linear in the
    padded rows, and a power of two pads a lookback of ~1.1M rows by 89%."""
    if n <= _MIN_ROWS:
        return _MIN_ROWS
    step = 1 << ((n - 1).bit_length() - 4)
    return _round_up(n, step)


def _vmem_working_set(hist: int, block_rows: int, feat: int) -> int:
    """Bytes of VMEM one grid step needs, fitted to what the v5e compiler
    accepts at the kernel's limit: about four f32 copies of the (F, H+B)
    extended block (carry, concatenation, matmul operand and the spills
    around them) plus one (H+B, B) window mask."""
    m = hist + block_rows
    return 4 * (4 * feat * m + m * block_rows)


def max_hist(feat: int, block_rows: int = _DEFAULT_BLOCK) -> int:
    """Deepest history (a power of two >= 128) whose working set fits three
    quarters of the kernel's VMEM limit for ``feat`` features — spans
    deeper than this take the XLA path.  At the default block this is
    16384 rows for up to 8 features, 4096 for 128, 1024 for 1024."""
    fp = _round_up(max(feat, 1), _SUBLANE)
    blk = _round_up(block_rows, _LANE)
    h = _LANE
    while _vmem_working_set(2 * h, blk, fp) <= VMEM_LIMIT_BYTES * 3 // 4:
        h *= 2
    return h


@functools.partial(jax.jit, static_argnames=("block_rows", "hist"))
def rolling_sum(
    values: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    block_rows: int = _DEFAULT_BLOCK,
    hist: int = _DEFAULT_BLOCK,
) -> jnp.ndarray:
    """Rolling-window sum.  values (N, F); starts (N,) int32; spans <= hist.

    Returns float32 (N, F).  Padding: rows to the (128-aligned) block
    multiple (pad rows use start=index so their window is empty+self over
    zero values), features to 8 sublanes of the transposed layout; ``hist``
    rounds up to 128 lanes.
    """
    n, feat = values.shape
    blk = _round_up(block_rows, _LANE)
    n_pad = _round_up(max(n, 1), blk)
    f_pad = _round_up(max(feat, 1), _SUBLANE)
    vals_t = jnp.zeros((f_pad, n_pad), jnp.float32)
    vals_t = vals_t.at[:feat, :n].set(values.astype(jnp.float32).T)
    starts_p = jnp.arange(n_pad, dtype=jnp.int32)
    starts_p = starts_p.at[:n].set(starts.astype(jnp.int32))
    out = rolling_sum_kernel_call(
        vals_t, starts_p[None, :], block_rows=blk, hist=_round_up(hist, _LANE)
    )
    return out[:feat, :n].T


def _two_sum(a: jnp.ndarray, b: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """a + b = s + err exactly in float32 (Knuth's TwoSum)."""
    s = a + b
    bv = s - a
    return s, (a - (s - bv)) + (b - bv)


def _add_double_float(x, y):
    """Sum of two (hi, lo) float32 pairs, renormalized so |lo| <= ulp(hi)/2."""
    s, e = _two_sum(x[0], y[0])
    e = e + (x[1] + y[1])
    hi = s + e
    return hi, e - (hi - s)


@jax.jit
def rolling_sum_xla(values: jnp.ndarray, starts: jnp.ndarray) -> jnp.ndarray:
    """O(N·F) prefix-difference on the XLA path (no Pallas): the windowed
    sum as P[i+1]-P[starts[i]] via a prefix scan + gather.  A float32
    prefix over millions of rows would carry errors far larger than a
    window's sum, so the prefix is a double-float (hi, lo) pair scan
    (about 48 significant bits) and the difference is taken on both
    halves.

    The TPU compiler's time for a scan grows with the scanned length (about
    400 s for one scan over 6 x 2^20 rows on v5e), so the rows are scanned
    in blocks of ``_SCAN_BLOCK`` and then the N / ``_SCAN_BLOCK`` block
    totals are scanned."""
    v = values.astype(jnp.float32)
    n, feat = v.shape
    nb = n // _SCAN_BLOCK + 1  # room for P[n], one past the last row
    v = jnp.pad(v, ((0, nb * _SCAN_BLOCK - n), (0, 0)))
    v = v.reshape(nb, _SCAN_BLOCK, feat)
    scan = functools.partial(jax.lax.associative_scan, _add_double_float)
    local = scan((v, jnp.zeros_like(v)), axis=1)  # inclusive, within a block
    totals = scan((local[0][:, -1], local[1][:, -1]), axis=0)
    zero = jnp.zeros((1, feat), jnp.float32)
    # offset[b] = sum of the blocks before b; shifted[i] = local sum at i - 1
    offset = [jnp.concatenate([zero, t[:-1]]) for t in totals]
    shifted = [jnp.concatenate([zero, t.reshape(-1, feat)]) for t in local]

    def prefix(i):  # P[i] = sum of rows [0, i), as a (hi, lo) pair
        b = i // _SCAN_BLOCK
        j = jnp.where(i % _SCAN_BLOCK == 0, 0, i)  # a block's first row: 0
        return _add_double_float(
            (offset[0][b], offset[1][b]), (shifted[0][j], shifted[1][j])
        )

    end_hi, end_lo = prefix(jnp.arange(1, n + 1))
    start_hi, start_lo = prefix(starts)
    return (end_hi - start_hi) + (end_lo - start_lo)


@functools.partial(jax.jit, static_argnames=("levels", "agg"))
def rolling_extreme(
    values: jnp.ndarray, starts: jnp.ndarray, *, levels: int, agg: str
) -> jnp.ndarray:
    """Windowed min/max in O(N·F·levels) on the XLA path: level k holds the
    extreme of the 2^k rows ending at each row (doubling), and a window of
    span L in [2^k, 2^(k+1)) is the extreme of two overlapping level-k
    windows, one ending at the row and one starting at its window start.
    ``levels`` = bit length of the largest span."""
    op, fill = (jnp.maximum, -jnp.inf) if agg == "max" else (jnp.minimum, jnp.inf)
    v = values.astype(jnp.float32)
    n, feat = v.shape
    table = [v]
    for k in range(1, levels):
        sh = 1 << (k - 1)
        prev = table[-1]
        shifted = jnp.concatenate([jnp.full((sh, feat), fill), prev[:-sh]])[:n]
        table.append(op(prev, shifted))
    table = jnp.stack(table)  # (levels, N, F)
    rows = jnp.arange(n)
    span = rows + 1 - starts
    k = (jnp.floor(jnp.log2(span.astype(jnp.float32)))).astype(jnp.int32)
    # float log2 can land one off at exact powers of two; correct it exactly
    k = k + ((1 << (k + 1)) <= span) - ((1 << k) > span)
    k = jnp.clip(k, 0, levels - 1)
    tail = table[k, rows]
    head = table[k, starts + (1 << k) - 1]
    return op(tail, head)


def _pick_hist(max_span: int) -> int:
    """Static history depth: next power of two >= the span (and >= 128
    lanes), so recompilation is bounded to O(log(max span)) variants."""
    h = _LANE
    while h < max_span:
        h *= 2
    return h


def rolling_agg(
    values,
    starts: np.ndarray,
    agg: str,
    *,
    block_rows: int = _DEFAULT_BLOCK,
    backend: str = "pallas",
    monitor=None,
) -> np.ndarray:
    """Public entry used by the DSL executor: ``out[i]`` aggregates the rows
    ``[starts[i], i]``.  ``starts`` must be host-side (numpy) — the DSL
    computes it from store-resident timestamps — which lets us pick the
    static history bucket and validate spans eagerly.

    The rows are padded on the host to ``row_bucket(N)`` (pad rows: value
    0, start = their own index, so no real row's window reaches them), the
    history depth is a power of two, the min/max doubling levels the span's
    bit length (at least ``_MIN_LEVELS``), and
    the result comes back to the host before the padding is cut off: a
    call compiles a new program only for a new bucket.  Returns a float32
    (N, F) numpy array.

    backend: 'pallas' (the kernel; compiled on TPU, interpreted elsewhere)
    or 'xla' (the cumsum formulation).  A 'pallas' sum whose spans are
    deeper than ``max_hist`` allows takes the XLA path too, and reports it
    through ``monitor.record_kernel_fallback`` when a monitor is given."""
    starts = np.asarray(starts)
    values = np.asarray(values)
    n, feat = values.shape
    if n == 0:
        return np.zeros((0, feat), np.float32)
    spans = np.arange(n) + 1 - starts
    if (spans <= 0).any():
        raise ValueError("window starts must satisfy starts[i] <= i")
    max_span = int(spans.max())

    if agg == "count":
        return np.broadcast_to(spans.astype(np.float32)[:, None], (n, feat)).copy()
    if agg not in ("sum", "mean", "min", "max"):
        raise ValueError(f"unknown agg {agg!r}")

    hist = _pick_hist(max_span)
    nb = row_bucket(n)
    vals = np.zeros((nb, feat), np.float32)
    vals[:n] = values
    starts_p = np.arange(nb, dtype=np.int32)
    starts_p[:n] = starts
    vals, starts_p = jnp.asarray(vals), jnp.asarray(starts_p)

    if agg in ("min", "max"):
        levels = max(max_span.bit_length(), _MIN_LEVELS)
        out = rolling_extreme(vals, starts_p, levels=levels, agg=agg)
        return np.asarray(out)[:n]

    deep = hist > max_hist(feat, block_rows)
    if backend == "pallas" and deep and monitor is not None:
        monitor.record_kernel_fallback("rolling_agg")
    if backend == "xla" or deep:
        s = rolling_sum_xla(vals, starts_p)
    else:
        s = rolling_sum(vals, starts_p, block_rows=block_rows, hist=hist)
    s = np.asarray(s)[:n]
    if agg == "sum":
        return s
    return s / np.maximum(spans, 1).astype(np.float32)[:, None]
