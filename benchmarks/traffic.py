"""Degree-factor exchange compression: measured wire words vs the §5
traffic model.

The paper's headline systems claim is that combining updates **at the
source shard** before they cross the inter-FPGA network cuts traffic by
roughly the average degree: every cut edge aimed at the same remote
vertex collapses into one (id, payload) wire entry. This benchmark
measures it end to end on a power-law (R-MAT) graph:

  * run the same BFS under ``exchange="unicast"`` (one word per cut
    edge) and ``exchange="combined"`` (one combined entry per distinct
    remote destination) on a 4-device mesh (subprocess — the main
    process keeps 1 CPU device);
  * assert the two runs are **bit-identical** and that steady-state
    re-submission **re-traces nothing**;
  * compare the measured reduction against the perfmodel's analytic
    prediction (uniform-partition shape estimates) and its exact-layout
    prediction (the engine's own padded ``e_pair_max``/``comb_max``),
    which must reproduce the measured counters to within 20%.

``GRAVFM_BENCH_CI=1`` turns the comparisons into gates (exit non-zero
on violation):
    measured reduction >= 5x          (avg degree 64 graph)
    measured reduction >= 0.8x of the analytic degree-factor prediction
    measured combined words within 20% of the exact-layout prediction
    bit-identical results, zero steady-state re-traces

The run always writes ``bench-traffic.json`` (or ``$GRAVFM_TRAFFIC_OUT``)
with the raw numbers; the CI workflow uploads it as a build artifact.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import emit

_SCRIPT = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.core import graph as G, partition as PT, algorithms as ALG
from repro.core.engine_shardmap import ShardEngine
from repro.launch.mesh import auto_mesh

SCALE, EDGE_FACTOR, P = %(scale)d, %(edge_factor)d, 4
g = G.rmat(SCALE, EDGE_FACTOR, seed=7)
pg = PT.partition_graph(g, P, method="greedy", pad_multiple=16)
mesh = auto_mesh((P,), ("graph",))

out = {"num_vertices": g.num_vertices, "num_edges": g.num_edges, "P": P}
state = {}
for exch in ("unicast", "combined"):
    eng = ShardEngine(ALG.bfs(), pg, mesh=mesh, exchange=exch,
                      backend="ref")
    r0 = eng.run(root=np.int32(0))          # traces
    traces_warm = eng.traces
    t0 = time.perf_counter()
    r1 = eng.run(root=np.int32(0))          # steady state
    wall = time.perf_counter() - t0
    state[exch] = {k: np.asarray(v) for k, v in r1["state"].items()}
    out[exch] = {
        "wire_words": float(r1["comm"]["wire_words"]),
        "supersteps": int(r1["supersteps"]),
        "messages": int(r1["messages"]),
        "wall_us": wall * 1e6,
        "retraced": eng.traces != traces_warm,
    }
    m = eng.meta
    out.setdefault("layout", {}).update(
        v_max=int(m.v_max), e_pair_max=int(m.e_pair_max),
        comb_max=int(m.comb_max))
out["identical"] = all(
    np.array_equal(state["unicast"][k], state["combined"][k])
    for k in state["unicast"])
print("TRAFFIC-JSON:" + json.dumps(out))
"""


def traffic():
    ci = bool(os.environ.get("GRAVFM_BENCH_CI"))
    scale, edge_factor = (10, 128)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SCRIPT % {"scale": scale, "edge_factor": edge_factor}
    # the child runs on forced host-platform devices and must never
    # contend with this process for an accelerator
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(src)
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError("traffic subprocess failed:\n"
                           + proc.stderr[-3000:])
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("TRAFFIC-JSON:"))
    meas = json.loads(line[len("TRAFFIC-JSON:"):])

    from repro.core import perfmodel as pm
    wl = pm.Workload(meas["num_vertices"], meas["num_edges"])
    P = meas["P"]
    lay = meas["layout"]
    # analytic prediction: uniform-partition shape estimates only
    red_analytic = pm.traffic_reduction(wl, P)
    # exact-layout prediction: the engine's own padded counters — must
    # reproduce the measured wire words (the counters ARE the layout)
    steps = meas["combined"]["supersteps"]
    pred_comb = steps * pm.words_per_superstep(
        "combined", wl, P, e_pair_max=lay["e_pair_max"],
        remote_dst_max=lay["comb_max"])["total"]
    pred_uni = steps * pm.words_per_superstep(
        "unicast", wl, P, e_pair_max=lay["e_pair_max"])["total"]
    w_uni = meas["unicast"]["wire_words"]
    w_comb = meas["combined"]["wire_words"]
    red_meas = w_uni / max(w_comb, 1e-9)
    model_err = abs(w_comb - pred_comb) / max(pred_comb, 1e-9)

    emit("traffic/rmat%d_ef%d/unicast" % (scale, edge_factor),
         meas["unicast"]["wall_us"],
         "wire_words=%.0f;modeled=%.0f" % (w_uni, pred_uni))
    emit("traffic/rmat%d_ef%d/combined" % (scale, edge_factor),
         meas["combined"]["wall_us"],
         "wire_words=%.0f;modeled=%.0f;model_err=%.3f"
         % (w_comb, pred_comb, model_err))
    emit("traffic/rmat%d_ef%d/reduction" % (scale, edge_factor), 0.0,
         "measured=%.2fx;analytic=%.2fx;identical=%s;retraced=%s"
         % (red_meas, red_analytic, meas["identical"],
            meas["unicast"]["retraced"] or meas["combined"]["retraced"]))

    out_path = os.environ.get("GRAVFM_TRAFFIC_OUT", "bench-traffic.json")
    with open(out_path, "w") as f:
        json.dump({"measured": meas, "predicted": {
            "combined_words": pred_comb, "unicast_words": pred_uni,
            "reduction_analytic": red_analytic},
            "reduction_measured": red_meas,
            "model_err": model_err}, f, indent=2)

    if ci:
        assert meas["identical"], "combined result != unicast result"
        assert not meas["unicast"]["retraced"], "unicast re-traced"
        assert not meas["combined"]["retraced"], "combined re-traced"
        assert red_meas >= 5.0, (
            "measured reduction %.2fx < 5x" % red_meas)
        assert red_meas >= 0.8 * red_analytic, (
            "measured %.2fx < 0.8 * analytic %.2fx"
            % (red_meas, red_analytic))
        assert model_err <= 0.20, (
            "measured combined words %.0f off exact-layout model %.0f "
            "by %.1f%%" % (w_comb, pred_comb, 100 * model_err))
