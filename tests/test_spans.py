"""Program spans and device scopes of the served batch path.

TraceBus interval spans (ring events with ``dur_s``, profiler
annotations, one flag read when disabled), the bucketed path's
``device_wait`` / ``execute`` / ``fetch`` / ``collect`` / ``resolve``
spans and per-query ``launch`` events under a contended dispatch lock,
the forming / device-wait split of ``QuerySpan`` and the Chrome trace,
and the superstep program's device scopes: every op of a compiled plan
mapped to its program and scope, with the compiled instructions,
results and trace counts those of the unscoped program."""
import contextlib
import re
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import algorithms as ALG
from repro.core import graph as G
from repro.core import partition as PT
from repro.core.engine import Engine
from repro.service import GraphQueryService, QueryRequest, TraceBus
from repro.service.trace import (SPAN_KINDS, assemble_spans, chrome_trace,
                                 hlo_op_scopes)

BATCH_SPANS = ("execute", "fetch", "collect", "resolve")


@pytest.fixture(scope="module")
def graph():
    return G.rmat(8, 8, seed=3).symmetrized()


def _service(graph, **kw):
    kw.setdefault("num_shards", 2)
    kw.setdefault("max_batch", 2)
    svc = GraphQueryService(**kw)
    svc.add_graph("g", graph)
    for k in ("bfs", "sssp"):
        svc.warm("g", k, batch_sizes=[kw["max_batch"]])
    return svc


def _by_batch(events):
    out = {}
    for e in events:
        if e.kind in SPAN_KINDS and "batch" in e.attrs:
            out.setdefault(e.attrs["batch"], {})[e.kind] = e
    return out


@pytest.fixture(scope="module")
def contended(graph):
    """A BFS and an SSSP batch submitted from two client threads while
    the dispatch lock is held for 0.2 s: both wait for the device."""
    svc = _service(graph)
    hold = 0.2
    futs = {}

    def client(kernel):
        futs[kernel] = [svc.submit(QueryRequest("g", kernel, {"root": r}))
                        for r in (1, 2)]

    svc._dispatch_lock.acquire()
    threads = [threading.Thread(target=client, args=(k,), name=f"client-{k}")
               for k in ("bfs", "sssp")]
    for t in threads:
        t.start()
    # both batches formed (every query submitted) before the hold starts
    deadline = time.monotonic() + 60
    while sum(e.kind == "submit" for e in svc.trace_snapshot()) < 4:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    time.sleep(hold)
    svc._dispatch_lock.release()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    for fs in futs.values():
        for f in fs:
            f.result(timeout=60)
    return svc, hold


# ---------------------------------------------------------------------------
# TraceBus spans
# ---------------------------------------------------------------------------

def test_span_records_interval_and_costs_nothing_disabled():
    bus = TraceBus()
    with bus.context(klass="c", batch=7, qids=[1, 2]):
        with bus.span("execute"):
            time.sleep(0.01)
        sp = bus.span("device_wait", ts=time.perf_counter() - 0.5)
        sp.end()
        sp.end()                        # a second end records nothing
    (ex, wait) = bus.snapshot()
    assert ex.kind == "execute" and ex.dur_s >= 0.01 and ex.qid is None
    assert ex.klass == "c" and ex.attrs["batch"] == 7
    assert ex.attrs["qids"] == [1, 2]
    assert ex.attrs["thread"] == threading.current_thread().name
    assert wait.dur_s >= 0.5 and wait.ts < ex.ts
    with pytest.raises(AssertionError):
        bus.span("not_a_span")

    off = TraceBus(enabled=False)
    with off.context(batch=1):
        s1, s2 = off.span("execute"), off.span("resolve")
        with s1:
            pass
        s2.end()
    assert s1 is s2                 # one shared no-op, nothing allocated
    assert len(off) == 0 and off.emitted == 0


def test_profiler_capture_shows_program_spans_on_cpu(graph, tmp_path):
    from jax.profiler import ProfileData
    svc = _service(graph)
    jax.profiler.start_trace(str(tmp_path))
    try:
        futs = [svc.submit(QueryRequest("g", "bfs", {"root": r}))
                for r in (3, 4)]
        for f in futs:
            f.result(timeout=60)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"gravfm." + k for k in ("device_wait",) + BATCH_SPANS} <= names


# ---------------------------------------------------------------------------
# the bucketed batch path
# ---------------------------------------------------------------------------

def test_batch_waiting_for_the_lock_records_its_device_wait(contended):
    svc, hold = contended
    ev = svc.trace_snapshot()
    at = {}
    for e in ev:
        if e.qid is not None:
            at.setdefault(e.qid, {})[e.kind] = e.ts
    assert len(at) == 4
    for qid, t in at.items():
        assert t["submit"] <= t["admit"] <= t["launch"] <= t["retire"], qid
    waits = [e for e in ev if e.kind == "device_wait"]
    assert len(waits) == 2
    # both batches formed while the lock was held; the second also
    # waited for the first to run
    assert all(w.dur_s >= 0.5 * hold for w in waits)
    assert {w.attrs["thread"] for w in waits} == {"client-bfs",
                                                  "client-sssp"}
    snap = svc.stats_snapshot()
    assert snap["device_wait_p95_ms"] >= snap["device_wait_p50_ms"] \
        >= 0.5 * hold * 1e3
    # queue wait stays batch formation only
    assert snap["queue_wait_p95_ms"] < snap["device_wait_p50_ms"]


def test_batch_spans_nest_in_order_between_launch_and_release(contended):
    svc, _ = contended
    ev = svc.trace_snapshot()
    launch = {e.qid: e.ts for e in ev if e.kind == "launch"}
    batches = _by_batch(ev)
    assert len(batches) == 2
    order = sorted(batches.values(), key=lambda b: b["device_wait"].ts
                   + b["device_wait"].dur_s)
    for b in order:
        wait = b["device_wait"]
        t = wait.ts + wait.dur_s
        assert {launch[q] for q in wait.attrs["qids"]} == {t}
        for kind in BATCH_SPANS:
            s = b[kind]
            assert s.ts >= t - 1e-9, kind
            assert s.klass == wait.klass and s.attrs["qids"] == \
                wait.attrs["qids"]
            t = s.ts + s.dur_s
    # the next batch takes the device only once the first released it
    first, second = order
    end = first["resolve"].ts + first["resolve"].dur_s
    assert second["device_wait"].ts + second["device_wait"].dur_s >= end


def test_query_span_and_chrome_trace_split_forming_from_device_wait(
        contended):
    svc, hold = contended
    ev = svc.trace_snapshot()
    spans = assemble_spans(ev)
    assert len(spans) == 4
    for sp in spans.values():
        assert sp.queued[1] == sp.device_wait[0]     # admit
        assert sp.device_wait[1] == sp.active[0][0]  # launch
        assert sp.device_wait_s() >= 0.5 * hold
        assert sp.device_wait_s() > sp.queued_s()
    doc = chrome_trace(ev)
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in slices}
    assert {"queued", "device_wait", "active"} | set(BATCH_SPANS) <= names
    qwait = [e for e in slices if e["name"] == "device_wait"
             and e.get("cat") == "query"]
    assert len(qwait) == 4 and all(e["dur"] >= 0.5 * hold * 1e6
                                   for e in qwait)


def test_tracing_off_records_no_spans(graph):
    svc = _service(graph, tracing=False)
    futs = [svc.submit(QueryRequest("g", "sssp", {"root": r}))
            for r in (1, 2)]
    for f in futs:
        f.result(timeout=60)
    assert svc.trace_snapshot() == []
    assert svc.stats_snapshot()["device_wait_p50_ms"] >= 0.0


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------

HLO = """HloModule jit_bfs_gravfm_batch4, entry_computation_layout={()}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/while/body/gravfm.apply/add"}
}

ENTRY %main.3 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation
  %reduce.2 = s32[] reduce(%p), metadata={op_name="jit(f)/vmap(gravfm.init)/reduce"}
  %copy.1 = f32[8]{0} copy(%fusion)
  ROOT %sum.4 = f32[8]{0} add(%copy.1, %p), metadata={op_name="jit(f)/while/body/gravfm.deliver/gravfm.stats/add"}
}
"""


def test_hlo_op_scopes_read_metadata_and_fusion_roots():
    module, scopes = hlo_op_scopes(HLO)
    assert module == "jit_bfs_gravfm_batch4"
    assert scopes["fusion"] == "gravfm.apply"      # its root's scope
    assert scopes["reduce.2"] == "gravfm.init"     # through vmap(...)
    assert scopes["sum.4"] == "gravfm.stats"       # innermost wins
    assert scopes["copy.1"] == "" and scopes["p"] == ""


def test_compiled_batch_programs_map_every_fusion_to_a_scope(graph):
    svc = _service(graph, max_batch=4)
    m = svc.op_scopes()
    programs = {p for p, _ in m}
    assert programs == {"jit_bfs_gravfm_batch4", "jit_sssp_gravfm_batch4"}
    for plan in svc.plans._plans.values():
        text = plan.engine.lower(4).compile().as_text()
        fusions = re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? fusion\(",
                             text, re.M)
        assert fusions
        assert all((plan.program, f) in m for f in fusions)
        assert "gravfm.deliver" in {m[(plan.program, f)] for f in fusions}
        # apply's few elementwise ops fuse where XLA puts them; some of
        # the program's ops carry its scope
        mine = {s for (p, _), s in m.items() if p == plan.program}
        assert {"gravfm.apply", "gravfm.cond", "gravfm.init"} <= mine


def _strip(hlo: str) -> str:
    """HLO text without metadata, the source tables or the module name."""
    keep, skip = [], False
    for line in hlo.splitlines():
        if line.startswith("FileNames"):
            skip = True
        elif line.startswith(("%", "ENTRY")):
            skip = False
        if skip or line.startswith("HloModule"):
            continue
        keep.append(re.sub(r",?\s*metadata=\{[^{}]*\}", "", line))
    return "\n".join(keep)


@pytest.fixture
def fresh_compiles():
    """Compile afresh: another test in the same process may have turned
    JAX's persistent compilation cache on (the benchmark harness does),
    and its key leaves out the metadata compared here, so the bare
    program would load the scoped program's executable."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("kernel", ["bfs", "sssp"])
def test_scopes_change_metadata_only(graph, kernel, monkeypatch,
                                     fresh_compiles):
    """The scoped program compiles to the instructions of the same
    program traced with every named scope a no-op, and answers the same
    bits; serving it traces nothing after warm-up."""
    pg = PT.partition_graph(graph, 2)
    roots = np.array([0, 5, 9, 17], np.int32)
    scoped = Engine(ALG.ALGORITHMS[kernel](), pg, backend="ref")
    text = scoped.lower_batch(4).compile().as_text()
    assert "gravfm.deliver" in text
    want = scoped.run_batch(root=roots)
    with monkeypatch.context() as mp:
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        bare = Engine(ALG.ALGORITHMS[kernel](), pg, backend="ref")
        bare_text = bare.lower_batch(4).compile().as_text()
        got = bare.run_batch(root=roots)
    assert "gravfm." not in bare_text
    assert _strip(text) == _strip(bare_text)
    for a, b in zip(want, got):
        assert a.supersteps == b.supersteps and a.messages == b.messages
        for k in a.state:
            assert np.array_equal(a.state[k], b.state[k]), k

    svc = _service(graph, max_batch=4)
    before = svc.stats_snapshot()["plan_traces"]
    for _ in range(2):
        futs = [svc.submit(QueryRequest("g", kernel, {"root": int(r)}))
                for r in roots]
        for f, w in zip(futs, want):
            res = f.result(timeout=60)
            for k in w.state:
                assert np.array_equal(res.state[k], w.state[k]), k
    assert svc.stats_snapshot()["plan_traces"] == before
