"""Plain reference for YCSB reads and updates: a latest-wins map.

Every record's values are a counter-based hash of (seed, key, version,
field), so the reference regenerates any row it needs instead of holding
the table.  Version 0 is the loaded row; the u-th acknowledged update of the
window writes version u.  A read that was submitted after update ``lo`` was
acknowledged and completed before update ``hi + 1`` was applied may return
any version written in between (it is linearizable), and nothing else.

NumPy only: nothing here imports the feature store.
"""

from __future__ import annotations

import numpy as np

__all__ = ["LatestWins", "record_values", "seed_words"]

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_K_KEY = np.uint32(0x9E3779B1)
_K_FIELD = np.uint32(0x85EBCA77)
_K_VER = np.uint32(0x27D4EB2F)
_K_SEED = np.uint32(0x68E31DA4)


def _fmix32(h: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer, wrapping in uint32."""
    h = h ^ (h >> np.uint32(16))
    h = h * _M1
    h = h ^ (h >> np.uint32(13))
    h = h * _M2
    return h ^ (h >> np.uint32(16))


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of up to 64 bits as two uint32 words."""
    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    return s & 0xFFFFFFFF, s >> 32


def record_values(seed: int, keys, versions, fields: int) -> np.ndarray:
    """(n, fields) float32 values of records ``keys`` at ``versions``: each
    is 24 random bits over 2**24, so every value is exact in float32 and
    almost none is exact in bfloat16."""
    lo, hi = seed_words(seed)
    with np.errstate(over="ignore"):
        s = _fmix32(np.asarray([lo], np.uint32) ^ _fmix32(
            np.asarray([hi], np.uint32) + _K_SEED))
        k = np.asarray(keys, np.int64).astype(np.uint32)[:, None]
        v = np.asarray(versions, np.int64).astype(np.uint32)[:, None]
        f = np.arange(fields, dtype=np.uint32)[None, :]
        h = _fmix32(s ^ (v * _K_VER))
        h = _fmix32(h ^ (k * _K_KEY))
        h = _fmix32(h ^ (f * _K_FIELD))
    return ((h >> np.uint32(8)).astype(np.float32) * np.float32(2.0**-24))


class LatestWins:
    """The acknowledged updates of a run, in order, per key."""

    def __init__(self) -> None:
        self._keys: list[int] = []

    def apply(self, key: int) -> int:
        """Record one acknowledged update; returns its version (1-based)."""
        self._keys.append(int(key))
        return len(self._keys)

    @property
    def applied(self) -> int:
        return len(self._keys)

    def _index(self):
        keys = np.asarray(self._keys, np.int64)
        vers = np.arange(1, len(keys) + 1, dtype=np.int64)
        order = np.lexsort((vers, keys))
        return keys[order], vers[order]

    def version_at(self, keys, applied) -> np.ndarray:
        """Version each key holds once ``applied`` updates were acknowledged."""
        keys = np.asarray(keys, np.int64)
        applied = np.asarray(applied, np.int64)
        if not self._keys:
            return np.zeros(len(keys), np.int64)
        ks, vs = self._index()
        # composite (key, version) search: last update of key with version <= applied
        span = np.int64(len(self._keys) + 2)
        comp = ks * span + vs
        pos = np.searchsorted(comp, keys * span + applied, side="right") - 1
        safe = np.maximum(pos, 0)
        hit = (pos >= 0) & (ks[safe] == keys)
        return np.where(hit, vs[safe], 0)

    def versions_between(self, key: int, lo: int, hi: int) -> list[int]:
        """Versions of ``key`` written after update ``lo`` up to ``hi``."""
        return [
            u for u in range(lo + 1, hi + 1) if self._keys[u - 1] == int(key)
        ]

    def last_versions(self) -> tuple[np.ndarray, np.ndarray]:
        """(keys, version) of every updated key's latest update."""
        if not self._keys:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        ks, vs = self._index()
        last = np.ones(len(ks), bool)
        last[:-1] = ks[1:] != ks[:-1]
        return ks[last], vs[last]
