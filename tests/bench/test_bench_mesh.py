"""The four-chip cell rehearsed on four host CPU devices: a tiny
Kronecker graph under ``g500-s20-mesh``'s own ``service`` key and the
``mix`` traffic, served by the shard engine over
``make_serving_mesh(4)``. The sound run is ``correct`` against the host
reference; the shard path broken underneath is not; and the per-shard
message counter adds up to what the single-host engine counts for the
same queries, on the bucketed and on the continuous path.

The device count is fixed when JAX starts, so everything runs in one
child process with ``--xla_force_host_platform_device_count=4``."""
import json
import os
import subprocess
import sys

import pytest

from conftest import REPO

_SCRIPT = r"""
import json, sys, time
T_START = time.perf_counter()
sys.path[:0] = [{repo!r}, {repo!r} + "/src", {repo!r} + "/tests/bench"]
from pathlib import Path
import jax, jax.numpy as jnp, numpy as np
from bench import harness
from conftest import tiny_root
from repro.core import engine_shardmap as SM
from repro.core.graph import Graph
from repro.service import GraphQueryService, QueryRequest

cfg = json.loads((Path({repo!r}) / "bench/configs/g500-s20-mesh.json")
                 .read_text())
# every answer of the window is compared: one altered lane of 32
# escapes a sample of 16 more often than not
cfg.update(scale=10, check_per_kernel=0)
root = tiny_root(Path({tmp!r}), configs={{"tiny-mesh": cfg}}, workloads=[
    {{"name": "tiny.mix-mesh", "config": "tiny-mesh", "traffic": "mix",
      "chips": 4, "why": "test"}}])


def exchange_dropped():
    # messages from a vertex on another shard are dropped
    orig = SM.ShardEngine._deliver_allgather

    def deliver(self, d, payload, active):
        me = jax.lax.axis_index(SM.AXIS)
        local = d.src_slot // self.meta.v_max == me
        return orig(self, d._replace(lane_valid=d.lane_valid & local),
                    payload, active)
    SM.ShardEngine._deliver_allgather = deliver
    return lambda: setattr(SM.ShardEngine, "_deliver_allgather", orig)


def answer_altered():
    # one vertex of the last lane's answer is altered where the shard
    # engine produces it
    orig = SM.ShardEngine.run_batch

    def run_batch(self, max_supersteps=None, overlap=False, **qa):
        out = orig(self, max_supersteps, overlap, **qa)
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
    SM.ShardEngine.run_batch = run_batch
    return lambda: setattr(SM.ShardEngine, "run_batch", orig)


out = {{}}
for fault in ("none", "exchange_dropped", "answer_altered"):
    undo = globals()[fault]() if fault != "none" else (lambda: None)
    try:
        res = harness.run_cell(root, "tiny.mix-mesh", 2 ** 31 + 29, 0.3,
                               False, T_START, require_tpu=False)
    finally:
        undo()
    out[fault] = {{k: res[k] for k in ("correct", "attempted", "failed",
                                      "metrics", "checks", "device")}}

# the same queries through the single-host Engine and the shard engine,
# batched and continuous
g = harness.generate_graph(harness.load_cell(root, "tiny.mix-mesh"), 0)
roots = np.flatnonzero(g.degrees() > 0)[:40]
counts = {{}}
for name, kw in (("engine", {{}}),
                 ("shard", cfg["service"]),
                 ("shard_continuous", dict(cfg["service"], max_batch=8,
                                           scheduling="continuous"))):
    svc = GraphQueryService(**kw)
    svc.add_graph("g", Graph(g.num_vertices, *g.directed()))
    futs = [svc.submit(QueryRequest("g", k, {{"root": int(r)}},
                                    deadline_ms=1e9))
            for k in ("bfs", "sssp") for r in roots]
    svc.flush()
    [f.result() for f in futs]
    snap = svc.stats_snapshot()
    counts[name] = {{"messages_total": snap["messages_total"],
                    "shard_messages_total": snap["shard_messages_total"],
                    "queries": snap["queries_completed"]}}
out["counts"] = counts
print("MESH-CELL " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_cell(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(repo=str(REPO),
                                              tmp=str(tmp))],
        capture_output=True, text=True, timeout=900, cwd=tmp,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [x for x in proc.stdout.splitlines()
            if x.startswith("MESH-CELL ")][-1]
    return json.loads(line[len("MESH-CELL "):])


def test_mesh_cell_is_correct_on_four_devices(mesh_cell):
    res = mesh_cell["none"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 64
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"gteps", "latency_p50_ms",
                                   "latency_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("fault", ["exchange_dropped", "answer_altered"])
def test_shard_path_fault_is_not_correct(mesh_cell, fault):
    res = mesh_cell[fault]
    assert not res["correct"], res["checks"]
    assert any(n["value"] > n["limit"] for n in res["checks"].values())


@pytest.mark.parametrize("path", ["shard", "shard_continuous"])
def test_shard_messages_add_up_to_the_engines_count(mesh_cell, path):
    counts = mesh_cell["counts"]
    want = counts["engine"]
    got = counts[path]
    assert got["queries"] == want["queries"] == 80
    assert want["shard_messages_total"] == []
    assert len(got["shard_messages_total"]) == 4
    assert all(n > 0 for n in got["shard_messages_total"])
    assert sum(got["shard_messages_total"]) == got["messages_total"] \
        == want["messages_total"]


# ---------------------------------------------------------------------------
# the cell's per-layer readers
# ---------------------------------------------------------------------------

DATA = REPO / "tests" / "bench" / "data"
MS = 1e6        # trace nanoseconds


def reader(name):
    from bench.harness import load_module
    return load_module(REPO / "bench" / "metrics" / f"{name}.py").read


def fake_run(*, chips=4, steps=None, counters=None, xplane=("trace", 1.0),
             op_scopes=None, t_loop=(1.9, 4.0)):
    import types
    counters = counters or {}
    if steps is not None:
        counters = {"start": {"supersteps_total": 0, **counters.get(
                        "start", {})},
                    "end": {"supersteps_total": steps, **counters.get(
                        "end", {})}}
    svc = types.SimpleNamespace()
    if op_scopes is not None:
        svc.op_scopes = lambda: op_scopes
    return types.SimpleNamespace(
        xplane=xplane, t_loop=t_loop, svc=svc, counters=counters,
        cell=types.SimpleNamespace(chips=chips),
        counter=lambda name, lo, hi: (counters[hi][name] - counters[lo][name]
                                      if counters else None))


def four_chips(monkeypatch):
    """Chips busy 100, 120, 90 and 90 ms inside the loop (host 1.9 s
    to 4.0 s; the clock mark at host 1 s sits at trace 5 s), each with
    20 ms of it under gravfm.exchange, and work outside the loop."""
    from bench import scopes as sc
    from bench import trace as tr
    host = [tr.Event(tr.CLOCK_MARK, 5000 * MS, 5000 * MS)]
    ops = {}
    for chip, busy in enumerate((100, 120, 90, 90)):
        ops[f"/device:TPU:{chip}"] = [
            tr.Event("gravfm.exchange", 6000 * MS, 6020 * MS),
            tr.Event("gravfm.deliver", 6020 * MS, (6000 + busy) * MS),
            tr.Event("gravfm.deliver", 9000 * MS, 9500 * MS)]  # after
    monkeypatch.setattr(tr, "load", lambda path: (
        {p: [tr.Event("op", e.start_ns, e.end_ns) for e in ev]
         for p, ev in ops.items()}, host))
    monkeypatch.setattr(sc, "load_scoped_ops",
                        lambda path, scopes: (ops, host))


def test_mesh_readers_take_each_chip_apart(monkeypatch):
    four_chips(monkeypatch)
    run = fake_run(steps=4, op_scopes={})
    # the slowest chip sets the superstep: 120 ms over 4 supersteps
    assert reader("mesh_superstep_ms")(run) == pytest.approx(30.0)
    assert reader("chip_busy_spread_pct")(run) == pytest.approx(
        30.0 / 100.0 * 100.0)
    # 20 ms on every chip, over 4 supersteps
    assert reader("exchange_ms")(run) == pytest.approx(5.0)


def test_mesh_readers_on_a_recorded_one_chip_trace():
    """On one chip the slowest chip is the only one: the superstep is
    the device's busy time per superstep, the spread is 0, and the
    one-chip program has no exchange scope to read."""
    from bench import trace as tr
    meta = json.loads((DATA / "v5e_spans.json").read_text())
    scopes = {(p, o): s for p, o, s in meta["op_scopes"]}
    path = DATA / "v5e_spans.xplane.pb"
    run = fake_run(chips=1, steps=meta["supersteps"], op_scopes=scopes,
                   xplane=(path, meta["t_mark"]), t_loop=tuple(meta["t_loop"]))
    dev, host = tr.load(path)
    off = tr.clock_offset_ns(host, meta["t_mark"])
    lo, hi = (t * 1e9 + off for t in meta["t_loop"])
    busy = tr.reduce_window(dev, host, lo, hi, sorted(dev)[:1]).busy_s
    assert reader("mesh_superstep_ms")(run) == pytest.approx(
        busy / meta["supersteps"] * 1e3)
    assert reader("chip_busy_spread_pct")(run) == 0.0
    assert reader("exchange_ms")(run) is None


def test_device_readers_read_nothing_without_a_trace():
    run = fake_run(steps=4, xplane=None, op_scopes={})
    for name in ("mesh_superstep_ms", "chip_busy_spread_pct",
                 "exchange_ms"):
        assert reader(name)(run) is None, name


@pytest.mark.parametrize("start,end,want", [
    ([10, 10, 10, 10], [110, 90, 130, 70], 120 / 90),
    ([], [5, 5, 5, 5], 1.0),            # the first shard batch
    ([], [], None),                     # one chip's Engine counts none
    (None, None, None),                 # a program without the counter
])
def test_shard_msgs_skew(start, end, want):
    counters = {"start": {}, "end": {}}
    if start is not None:
        counters = {"start": {"shard_messages_total": start},
                    "end": {"shard_messages_total": end}}
    got = reader("shard_msgs_skew")(fake_run(counters=counters))
    assert got == (None if want is None else pytest.approx(want))
