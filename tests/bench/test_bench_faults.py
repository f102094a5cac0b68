"""A whole run on the CPU, past the harness's look for a chip, with the
served path broken underneath: each fault a cell can have has to come
out as ``correct`` false, and the unbroken run as true."""
import time

import numpy as np
import pytest

from bench import harness
from repro.core import engine as E
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def no_messages(monkeypatch):
    """Every superstep delivers nothing: the state stays as it began."""
    orig = kref.segment_combine

    def combine(vals, seg_ids, num_segments, combiner):
        out = orig(vals, seg_ids, num_segments, combiner)
        return np.full(out.shape, kops.identity_for(combiner, out.dtype))

    monkeypatch.setattr(kref, "segment_combine", combine)


def half_batch(monkeypatch):
    """The second half of each batch's lanes is left out and answered
    with the first half's results."""
    orig = E.Engine.run_batch

    def run_batch(self, max_supersteps=None, **qa):
        qa = {k: np.asarray(v) for k, v in qa.items()}
        keep = {k: np.concatenate([v[:(v.size + 1) // 2]] * 2)[:v.size]
                for k, v in qa.items()}
        return orig(self, max_supersteps, **keep)

    monkeypatch.setattr(E.Engine, "run_batch", run_batch)


def local_only(monkeypatch):
    """The exchange between shards is left out: messages from a vertex
    on another shard are dropped."""
    orig = E.Engine._deliver_gravfm

    def deliver(self, data, payload, active):
        data = data._replace(lane_valid=data.lane_valid & ~data.lane_remote)
        return orig(self, data, payload, active)

    monkeypatch.setattr(E.Engine, "_deliver_gravfm", deliver)


def altered_answer(monkeypatch):
    """One vertex of the last lane's answer is altered where the engine
    produces it."""
    orig = E.Engine.run_batch

    def run_batch(self, max_supersteps=None, **qa):
        out = orig(self, max_supersteps, **qa)
        st = dict(out[-1].state)
        if "dist" in st:
            d = st["dist"].copy()
            v = np.flatnonzero(np.isfinite(d) & (d > 0))
            d[v[-1:]] *= np.float32(1.001)
            st["dist"] = d
        else:
            p = st["parent"].copy()
            v = np.flatnonzero((p >= 0) & (p != np.arange(p.size)))
            p[v[-1:]] = -1
            st["parent"] = p
        out[-1].state = st
        return out

    monkeypatch.setattr(E.Engine, "run_batch", run_batch)


FAULTS = {"state_unchanged": no_messages, "half_batch": half_batch,
          "exchange_dropped": local_only, "answer_altered": altered_answer}


def run_tiny(root, seed=2 ** 31 + 17):
    return harness.run_cell(root, "tiny.mix", seed, 0.3, False,
                            time.perf_counter(), require_tpu=False)


def test_sound_run_is_correct(tiny_mix_root):
    res = run_tiny(tiny_mix_root)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert set(res["metrics"]) == {"gteps", "latency_p50_ms",
                                   "latency_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(tiny_mix_root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    res = run_tiny(tiny_mix_root)
    assert not res["correct"], res["checks"]
    assert any(n["value"] > n["limit"] for n in res["checks"].values())
