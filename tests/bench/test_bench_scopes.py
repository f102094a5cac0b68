"""The per-layer metrics that read the program's own spans and device
scopes: each reads a number from synthetic span events or from a trace
recorded on one TPU v5e chip, and nothing where the program (an older
commit) records no spans or a run has no device plane."""
import json
import types
from pathlib import Path

import pytest

from bench import scopes as sc
from bench.harness import GID, Window, load_module
from repro.service import TraceEvent

DATA = Path(__file__).resolve().parent / "data"
METRICS = Path(__file__).resolve().parents[2] / "bench" / "metrics"
NEW = ("device_wait_ms", "batch_host_ms", "edge_pass_ms", "graph_build_s",
       "warm_run_s")


def reader(name):
    return load_module(METRICS / f"{name}.py").read


def span(kind, ts, dur, batch=None, **attrs):
    if batch is not None:
        attrs["batch"] = batch
    return TraceEvent(kind=kind, ts=ts, dur_s=dur, attrs=attrs)


def fake_run(events, *, t_open=10.0, t_close=20.0, xplane=None,
             op_scopes=None, steps=None, t_loop=(0.0, 30.0)):
    svc = types.SimpleNamespace()
    if op_scopes is not None:
        svc.op_scopes = lambda: op_scopes
    counters = {}
    if steps is not None:
        counters = {"start": {"supersteps_total": 0},
                    "end": {"supersteps_total": steps}}
    return types.SimpleNamespace(
        events=events, window=Window(t_open, t_close, []), svc=svc,
        xplane=xplane, cell=types.SimpleNamespace(chips=1), t_loop=t_loop,
        counter=lambda name, lo, hi: (counters[hi][name]
                                      - counters[lo][name]
                                      if counters else None))


SETUP = [span("partition", 1.0, 2.0, graph_id=GID),
         span("engine_build", 3.0, 0.5, graph_id=GID, kernel="bfs"),
         span("engine_build", 3.5, 0.25, graph_id=GID, kernel="sssp"),
         span("engine_build", 3.8, 9.0, graph_id="other", kernel="bfs"),
         span("warm_run", 4.0, 1.5), span("warm_run", 6.0, 2.5)]
# batch 1 launches before the window, 2 and 3 inside it, 4 after it
BATCHES = [e for b, launch, wait in ((1, 9.0, 1.0), (2, 12.0, 2.0),
                                     (3, 15.0, 4.0), (4, 21.0, 3.0))
           for e in (span("device_wait", launch - wait, wait, b),
                     span("execute", launch, 1.0, b),
                     span("fetch", launch + 1.0, 0.1 * b, b),
                     span("collect", launch + 1.5, 0.2 * b, b),
                     span("resolve", launch + 2.0, 0.3 * b, b))]


def test_span_metrics_read_the_batches_launched_in_the_window():
    run = fake_run(SETUP + BATCHES)
    assert reader("device_wait_ms")(run) == pytest.approx(3000.0)
    # (0.6 * 2 + 0.6 * 3) s over two batches
    assert reader("batch_host_ms")(run) == pytest.approx(1500.0)
    assert reader("graph_build_s")(run) == pytest.approx(2.75)
    assert reader("warm_run_s")(run) == pytest.approx(4.0)
    assert sc.batches_launched_in_window(run) == {2: 12.0, 3: 15.0}


def test_metrics_read_nothing_from_a_program_without_spans():
    # an older program: lifecycle events only, no op_scopes, no spans
    events = [TraceEvent(kind="admit", ts=12.0, qid=1),
              TraceEvent(kind="retire", ts=13.0, qid=1)]
    run = fake_run(events, xplane=(DATA / "v5e_small.xplane.pb", 30.67),
                   steps=10)
    assert all(reader(m)(run) is None for m in NEW)


def test_edge_pass_is_deliver_self_time_per_superstep(monkeypatch):
    from bench import trace as tr
    ms = 1e6     # trace nanoseconds
    # the clock mark at host t=1 s sits at trace 5 s: offset 4 s
    host = [tr.Event(tr.CLOCK_MARK, 5000 * ms, 5000 * ms)]
    ops = [tr.Event(sc.NO_SCOPE, 6000 * ms, 6100 * ms),      # the while
           tr.Event("gravfm.deliver", 6010 * ms, 6040 * ms),
           tr.Event("gravfm.apply", 6040 * ms, 6050 * ms),
           tr.Event("gravfm.deliver", 6060 * ms, 6080 * ms),
           tr.Event("gravfm.deliver", 9000 * ms, 9500 * ms)]  # after loop
    monkeypatch.setattr(sc, "load_scoped_ops", lambda path, scopes: (
        {"/device:TPU:0": ops}, host))
    run = fake_run([], xplane=("trace", 1.0), op_scopes={}, steps=5,
                   t_loop=(1.9, 4.0))
    assert sc.scope_seconds(run, *run.t_loop) == pytest.approx(
        {sc.NO_SCOPE: 0.04, "gravfm.deliver": 0.05, "gravfm.apply": 0.01})
    assert reader("edge_pass_ms")(run) == pytest.approx(10.0)


@pytest.fixture(scope="module")
def recorded():
    """Two BFS and two SSSP batches of 4 served on one TPU v5e chip
    under the profiler, with the service's op-to-scope map and spans."""
    meta = json.loads((DATA / "v5e_spans.json").read_text())
    scopes = {(p, o): s for p, o, s in meta["op_scopes"]}
    return meta, scopes


def test_recorded_ops_fall_under_the_program_scopes(recorded):
    meta, scopes = recorded
    device, host = sc.load_scoped_ops(DATA / "v5e_spans.xplane.pb", scopes)
    assert meta["device_kind"] == "TPU v5 lite"
    assert list(device) == ["/device:TPU:0"]
    assert {e.name for e in device["/device:TPU:0"]} >= {
        "gravfm.deliver", "gravfm.cond"}
    names = {e.name for e in host}
    assert {"gravfm.execute", "gravfm.fetch", "gravfm.collect",
            "gravfm.resolve", "gravfm.device_wait"} <= names
    run = fake_run([], xplane=(DATA / "v5e_spans.xplane.pb", meta["t_mark"]),
                   op_scopes=scopes, t_loop=tuple(meta["t_loop"]),
                   steps=meta["supersteps"])
    by_scope = sc.scope_seconds(run, *run.t_loop)
    busy = sum(by_scope.values())
    # the loop's few small helper programs (argument conversions) are
    # not plans, so the map does not know them
    assert by_scope.get(sc.UNMAPPED, 0.0) < 0.01 * busy
    assert sum(v for k, v in by_scope.items() if k.startswith("gravfm.")) \
        >= 0.9 * busy
    edge = reader("edge_pass_ms")(run)
    assert 0 < edge < busy / meta["supersteps"] * 1e3


def test_edge_pass_reads_nothing_without_a_device_plane(recorded):
    _, scopes = recorded
    assert reader("edge_pass_ms")(fake_run([], op_scopes=scopes,
                                           steps=10)) is None
