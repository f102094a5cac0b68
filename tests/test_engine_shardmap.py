"""Multi-device shard_map engine: all five exchange schedules must be
bit-identical to the global-array engine.

Needs >1 device, so the check runs in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest
process keeps the default 1 CPU device per the assignment rules)."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import jax, numpy as np
from repro.core import graph as G, partition as PT, algorithms as ALG
from repro.core.engine import Engine
from repro.core.engine_shardmap import ShardEngine

from repro.launch.mesh import auto_mesh
mesh = auto_mesh((8,), ("graph",))
g = G.uniform(300, 6.0, seed=3).symmetrized()
pg = PT.partition_graph(g, 8, method="greedy", pad_multiple=16)

ref = Engine(ALG.wcc(), pg, mode="gravfm", backend="ref").run()
for exch in ("allgather", "ring", "frontier", "unicast", "combined"):
    out = ShardEngine(ALG.wcc(), pg, mesh=mesh, exchange=exch,
                      backend="ref").run()
    assert np.array_equal(out["state"]["label"], ref.state["label"]), exch
    assert out["messages"] == ref.messages, exch

# pallas kernel inside shard_map
out = ShardEngine(ALG.wcc(), pg, mesh=mesh, exchange="allgather",
                  backend="pallas", tile_e=64, tile_r=32).run()
assert np.array_equal(out["state"]["label"], ref.state["label"])

# pallas segment-combine driving BOTH levels of the combined exchange
# (source-side per-destination fold + receiver-side merge)
out = ShardEngine(ALG.wcc(), pg, mesh=mesh, exchange="combined",
                  backend="pallas", tile_e=64, tile_r=32).run()
assert np.array_equal(out["state"]["label"], ref.state["label"])

# combine-at-source must move fewer words than per-edge unicast once
# many cut edges share a destination: dense power-law R-MAT (avg degree
# 64 over 8 shards -> ~8 edges per (pair, destination) bucket slot)
gd = G.rmat(8, 64, seed=1)
pgd = PT.partition_graph(gd, 8, method="greedy", pad_multiple=16)
uni = ShardEngine(ALG.wcc(), pgd, mesh=mesh, exchange="unicast",
                  backend="ref").run()
comb = ShardEngine(ALG.wcc(), pgd, mesh=mesh, exchange="combined",
                   backend="ref").run()
assert np.array_equal(comb["state"]["label"], uni["state"]["label"])
assert comb["exchange_words"] < uni["exchange_words"], (
    comb["exchange_words"], uni["exchange_words"])

# SSSP (distances, then the finalize's parents) through every schedule
gw = G.uniform(200, 5.0, seed=4, weighted=True).symmetrized()
pgw = PT.partition_graph(gw, 8, method="round_robin", pad_multiple=16)
refs = Engine(ALG.sssp(0), pgw, mode="gravfm", backend="ref").run()
for exch in ("allgather", "ring", "unicast", "combined"):
    out = ShardEngine(ALG.sssp(0), pgw, mesh=mesh, exchange=exch,
                      backend="ref").run()
    assert np.allclose(out["state"]["dist"], refs.state["dist"],
                       equal_nan=True), exch
    assert np.array_equal(out["state"]["parent"], refs.state["parent"]), exch

# frontier compression must move fewer words than dense broadcast on a
# sparse-frontier workload (BFS on a ladder: <=33 active/superstep while
# the dense array is v_max=400+ words/superstep; capacity floor is 64)
gl = G.ladder(32, 100, 1, seed=0)
pgl = PT.partition_graph(gl, 8, pad_multiple=16)
dense = ShardEngine(ALG.bfs(0), pgl, mesh=mesh, exchange="allgather",
                    backend="ref").run()
compact = ShardEngine(ALG.bfs(0), pgl, mesh=mesh, exchange="frontier",
                      backend="ref").run()
assert np.array_equal(dense["state"]["parent"], compact["state"]["parent"])
assert compact["exchange_words"] < dense["exchange_words"], (
    compact["exchange_words"], dense["exchange_words"])

# batched multi-query execution through the explicit collectives: every
# exchange must match per-root single-query Engine runs exactly
roots = np.array([0, 5, 17, 100, 250, 7, 99, 3], np.int32)
for exch in ("allgather", "ring", "frontier", "unicast", "combined"):
    se = ShardEngine(ALG.bfs(), pg, mesh=mesh, exchange=exch, backend="ref")
    outs = se.run_batch(root=roots)
    for i, r in enumerate(roots):
        rr = Engine(ALG.bfs(int(r)), pg, mode="gravfm", backend="ref").run()
        assert np.array_equal(outs[i]["state"]["parent"],
                              rr.state["parent"]), (exch, r)
        assert outs[i]["supersteps"] == rr.supersteps, (exch, r)
        assert outs[i]["messages"] == rr.messages, (exch, r)

# continuous stepping through the explicit collectives: a query spliced
# into the in-flight slot array at superstep t must match a solo run
# exactly, for every exchange schedule; slot recycling re-traces nothing
for exch in ("allgather", "ring", "frontier", "unicast", "combined"):
    se = ShardEngine(ALG.bfs(), pg, mesh=mesh, exchange=exch, backend="ref")
    st = se.make_stepper(4)
    qkw = {{"root": np.zeros(4, np.int32)}}
    carry, act, steps = st.init(qkw)
    occ = np.zeros(4, bool); occ[0] = True        # lane 0: root 0
    for _ in range(2):
        carry, act, steps = st.step(carry, occ)
    qkw["root"][1] = 100                          # joins at superstep 2
    fresh = np.zeros(4, bool); fresh[1] = True
    carry, act, steps = st.admit(carry, qkw, fresh)
    occ[1] = True
    traces_steady = se.traces
    for _ in range(1000):
        occ &= act
        if not occ.any():
            break
        carry, act, steps = st.step(carry, occ)
    else:
        raise AssertionError(exch + " did not quiesce")
    host = st.fetch(carry)
    for lane, root in ((0, 0), (1, 100)):
        res = se.lane_result(host, lane)
        rr = Engine(ALG.bfs(int(root)), pg, mode="gravfm",
                    backend="ref").run()
        assert np.array_equal(res["state"]["parent"],
                              rr.state["parent"]), (exch, lane)
        assert res["supersteps"] == rr.supersteps, (exch, lane)
        assert res["messages"] == rr.messages, (exch, lane)
    assert se.traces == traces_steady, exch      # zero steady-state traces
print("SHARDMAP-SUBPROCESS-OK")
"""


@pytest.mark.slow
def test_shardmap_engine_multidevice():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SCRIPT.format(src=os.path.abspath(src))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDMAP-SUBPROCESS-OK" in proc.stdout


_OVERLAP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import numpy as np
from repro.core import graph as G, partition as PT, algorithms as ALG
from repro.core.engine import Engine
from repro.core.engine_shardmap import ShardEngine
from repro.launch.mesh import auto_mesh

mesh = auto_mesh((8,), ("graph",))
# weighted so SSSP's distances exercise each schedule's min fold
# through the windowed pipeline's per-window merge
gw = G.uniform(300, 6.0, seed=3, weighted=True).symmetrized()
pg = PT.partition_graph(gw, 8, method="greedy", pad_multiple=16)

for name, kern in (("bfs", ALG.bfs(0)), ("sssp", ALG.sssp(0))):
    ref = Engine(kern, pg, mode="gravfm", backend="ref").run()
    for exch in ("allgather", "ring", "frontier", "unicast", "combined"):
        se = ShardEngine(kern, pg, mesh=mesh, exchange=exch,
                         backend="ref")
        sync = se.run()
        ov = se.run(overlap=True)
        warm = se.traces
        # steady state AND per-run toggling re-trace nothing: both
        # programs share the engine's device graph
        se.run(overlap=True); se.run(); se.run(overlap=True)
        assert se.traces == warm, (name, exch, "re-traced")
        for s in sync["state"]:
            a, b = np.asarray(sync["state"][s]), np.asarray(ov["state"][s])
            assert np.array_equal(a, b, equal_nan=True), (name, exch, s)
            assert np.array_equal(
                b, np.asarray(ref.state[s]), equal_nan=True), (name, exch, s)
        assert ov["supersteps"] == sync["supersteps"] == ref.supersteps, (
            name, exch)
        assert ov["messages"] == sync["messages"] == ref.messages, (
            name, exch)

# service level: per-request overlap toggling at steady state re-traces
# nothing once both plans are warm
from repro.service import GraphQueryService, QueryRequest
svc = GraphQueryService(num_shards=4, exchange="combined",
                        scheduling="continuous", slots=4)
svc.add_graph("g", gw)
svc.warm("g", "bfs")
svc.warm("g", "bfs", overlap=True)
t0 = svc.stats_snapshot()["plan_traces"]
base = None
for i in range(8):
    req = QueryRequest("g", "bfs", {{"root": (i // 2) % 3}},
                       deadline_ms=1e9, overlap=(i % 2 == 1))
    fut = svc.submit(req)
    svc.flush()
    res = fut.result()
    if i % 2 == 0:
        base = res
    else:
        assert np.array_equal(res.state["parent"], base.state["parent"])
        assert res.supersteps == base.supersteps
assert svc.stats_snapshot()["plan_traces"] == t0, "service re-traced"
print("SHARDMAP-OVERLAP-OK")
"""


@pytest.mark.slow
def test_shardmap_overlap_multidevice():
    """Pipelined (overlapped) exchange schedules: bit-identical to the
    synchronous schedules and the global-array engine for all five
    exchanges x {BFS, SSSP}, with zero re-traces when toggling overlap
    per run — and per request through the serving stack."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _OVERLAP_SCRIPT.format(src=os.path.abspath(src))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDMAP-OVERLAP-OK" in proc.stdout


_UPLOAD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {src!r})
import jax, numpy as np
from repro.core import graph as G, partition as PT, algorithms as ALG
from repro.core.engine_shardmap import ShardEngine
from repro.launch.mesh import make_serving_mesh

def device_bytes():
    out = dict.fromkeys(jax.devices(), 0)
    for a in jax.live_arrays():
        shard = np.prod(a.sharding.shard_shape(a.shape), dtype=int)
        for d in a.sharding.device_set:
            out[d] += int(shard) * a.dtype.itemsize
    return [out[d] for d in jax.devices()]

samples = []
put = jax.device_put

def watched_put(*args, **kwargs):
    # what each device holds as an upload starts and once it is done
    samples.append(device_bytes())
    out = put(*args, **kwargs)
    jax.block_until_ready(out)
    samples.append(device_bytes())
    return out

jax.device_put = watched_put
g = G.rmat(9, 8, seed=2, weighted=True).symmetrized()
pg = PT.partition_graph(g, 4)
eng = ShardEngine(ALG.sssp(), pg, mesh=make_serving_mesh(4),
                  exchange="allgather", backend="ref")
after_build = device_bytes()
assert eng.offload() > 0 and device_bytes() == [0, 0, 0, 0]
eng.upload()
assert device_bytes() == after_build, (device_bytes(), after_build)
assert after_build[0] > 0 and len(samples) >= 4
for b in samples:
    assert all(b[0] <= x for x in b[1:]), b
print("SHARDMAP-UPLOAD-OK", after_build)
"""


@pytest.mark.slow
def test_shard_upload_never_stages_on_device_0():
    """ShardEngine's construction and its re-upload after ``offload``
    send each shard straight from the host to its device: device 0
    never holds more bytes than any other device, before, during or
    after an upload."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _UPLOAD_SCRIPT.format(src=os.path.abspath(src))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDMAP-UPLOAD-OK" in proc.stdout
