"""The YCSB cells at a tiny size on the CPU: the program agrees with the
latest-wins reference, the bfloat16 control does not, and each fault the
cell can have turns ``correct`` false."""

from __future__ import annotations

import numpy as np
import pytest

from bench.tests import tiny

CONFIG = "ycsb_1kb"


@pytest.mark.parametrize("traffic", ["ycsb_b_zipf", "ycsb_c_uniform"])
def test_program_correct_and_control_fails(traffic):
    out = tiny.run(CONFIG, traffic, control=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["control_correct"], out["control_checks"]
    assert out["control_checks"]["gets_wrong"]["value"] > 0


def _skip_updates(monkeypatch):
    """An update that returns with the table unchanged."""
    from repro.core.online_store import OnlineStore

    merge = OnlineStore.merge

    def merge_unless_update(self, spec, frame, creation_ts, **kw):
        if len(frame) == 1:
            return self._empty_stats("kernel", len(spec.features), creation_ts)
        return merge(self, spec, frame, creation_ts, **kw)

    monkeypatch.setattr(OnlineStore, "merge", merge_unless_update)


def _half_batch(monkeypatch):
    """A dispatch whose second half of keys is left out of the lookup."""
    from repro.core.online_store import OnlineStore

    lookup = OnlineStore.lookup_encoded

    def half(self, name, version, ids, **kw):
        vals, found, cr = lookup(self, name, version, ids, **kw)
        cut = (len(ids) + 1) // 2
        vals[cut:] = 0.0
        found[cut:] = False
        return vals, found, cr

    monkeypatch.setattr(OnlineStore, "lookup_encoded", half)


def _altered_answer(monkeypatch):
    """One value of each dispatch altered where the lookup produces it."""
    from repro.core.online_store import OnlineStore

    lookup = OnlineStore.lookup_encoded

    def altered(self, name, version, ids, **kw):
        vals, found, cr = lookup(self, name, version, ids, **kw)
        if len(vals):
            vals[0, 0] += np.float32(1.0)
        return vals, found, cr

    monkeypatch.setattr(OnlineStore, "lookup_encoded", altered)


@pytest.mark.parametrize(
    "fault", [_skip_updates, _half_batch, _altered_answer],
    ids=["state_unchanged", "half_batch", "altered_answer"],
)
def test_fault_turns_correct_false(monkeypatch, fault):
    fault(monkeypatch)
    out = tiny.run(CONFIG, "ycsb_b_zipf", seconds=0.8)
    assert not out["correct"], out["checks"]
