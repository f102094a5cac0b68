"""The harness: cells found by name, files dropped in found by name, the
window cut on batch boundaries, and the refusal to run without a TPU."""
import json
import math
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness
from conftest import REPO, TINY_MIX, tiny_kronecker, tiny_root

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = harness.load_cell(REPO, cell)
    assert (REPO / "bench" / "graphs" / f"{c.config['graph']}.py").exists()
    assert c.traffic["clients"] and c.traffic["deadline_ms"] > 0
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_benchmark_json_keeps_to_its_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    configs = {c["name"] for c in SPEC["configs"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    assert configs == {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/") and (REPO / c["file"]).exists()
        assert all(NAME.match(k) for k in c["reduced"])
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
    # 2 + 14 runs per cell, each run_seconds + 60 s, 2 x 90 s of compile
    # per cell and 1200 s spare fit 43200 s at the full 24 cells
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    for p in SPEC["paths"]:
        assert (REPO / p).is_dir()
    assert (REPO / SPEC["command"][1]).is_file()


def test_dropped_in_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tiny_root(
        tmp_path, configs={"dropped": tiny_kronecker()},
        traffic={"dropped-bfs": dict(TINY_MIX, clients=[
            {"kernel": "bfs", "count": 4}])},
        metrics={"answered_in_window":
                 "def read(run):\n    return len(run.window.queries)\n"},
        workloads=[{"name": "dropped.bfs", "config": "dropped",
                    "traffic": "dropped-bfs", "chips": 1, "why": "test"}])
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "answered_in_window", "unit": "queries", "better": "higher",
        "source": "host_clock", "layer": "scheduler", "moves": "gteps",
        "workloads": ["dropped.bfs"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell(root, "dropped.bfs")
    assert cell.config["scale"] == 9 and len(cell.traffic["clients"]) == 1
    res = harness.run_cell(root, "dropped.bfs", 31, 0.2, True,
                           time.perf_counter(), require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["answered_in_window"]["value"] >= 4
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert set(res["checks"]) == {"missing", "bfs_bad_vertices"}


def answers(*times, cancelled=0):
    qs = [harness.Query(0, "bfs", 1, 0.0, t_done=t) for t in times]
    qs += [harness.Query(0, "bfs", 1, 0.0, cancelled=True)
           for _ in range(cancelled)]
    return qs


def test_window_is_cut_on_batch_boundaries():
    # answers come in batches; the window opens at the first answer and
    # closes at the first answer 50 s later, which it leaves out
    qs = answers(10.0, 10.1, 30.0, 30.2, 45.0, 45.1, 60.0, 60.3, 80.0)
    w = harness.cut_window(qs, 50.0)
    assert (w.t_open, w.t_close) == (10.0, 60.0)
    assert [q.t_done for q in w.queries] == [10.0, 10.1, 30.0, 30.2,
                                             45.0, 45.1]
    with pytest.raises(RuntimeError):
        harness.cut_window(qs, 100.0)


def test_window_of_a_mix_closes_on_the_kernel_that_opened_it():
    # bfs batches answer at 1, 5, 9, ... and sssp batches at 4, 8, ...:
    # the window holds whole rounds, one sssp answer per bfs answer
    qs = []
    for r in range(8):
        qs += answers(1.0 + 4 * r)
        qs += [harness.Query(0, "sssp", 1, 0.0, t_done=4.0 + 4 * r)]
    w = harness.cut_window(qs, 10.0)
    assert (w.t_open, w.t_close) == (1.0, 13.0)
    kinds = [q.kernel for q in w.queries]
    assert kinds.count("bfs") == kinds.count("sssp") == 3


def test_unanswered_batches_stay_out_of_the_window():
    qs = answers(10.0, 10.0, 25.0, 25.0, 60.0, cancelled=2)
    qs[2].t_done = math.nan      # sent in the window, never answered
    w = harness.cut_window(qs, 30.0)
    assert [q.t_done for q in w.queries] == [10.0, 10.0, 25.0]


@pytest.mark.parametrize("clients,max_batch,want", [
    (64, 32, [32]), (32, 32, [32]), (48, 32, None), (3, 32, None)])
def test_warm_takes_only_the_batches_a_closed_loop_makes(clients, max_batch,
                                                          want):
    assert harness.warm_sizes(clients, max_batch) == want


def test_answers_group_into_the_batches_the_clients_saw():
    qs = answers(10.0, 10.1, 10.2, 30.0, 30.4, 45.0)
    qs[-1].t_done = math.nan
    assert harness.answer_groups(qs) == [(10.0, 3), (30.0, 2)]


def test_closed_loop_keeps_its_clients_and_roots_apart():
    class Svc:
        """Answers every query within a millisecond, as a result cache
        would."""
        def submit(self, req):
            time.sleep(1e-3)
            f = harness.cf.Future()
            f.set_running_or_notify_cancel()
            f.set_result(type("R", (), {"supersteps": 1, "state": {
                "parent": np.zeros(3, np.int32)}})())
            return f

    loop = harness.ClosedLoop(Svc(), ["bfs", "sssp", "bfs"],
                              np.arange(100, 400), deadline_ms=1e3,
                              seconds=0.3)
    loop.run(timeout=30.0)
    by_client = {}
    for q in loop.queries:
        by_client.setdefault(q.client, []).append(q)
    assert sorted(by_client) == [0, 1, 2]
    for c, qs in by_client.items():
        # client c of 3 sends roots c, c + 3, c + 6, ... of the order
        want = (c + 3 * np.arange(len(qs))) % 300 + 100
        assert [q.root for q in qs] == want.tolist()
        assert all(q.kernel == ["bfs", "sssp", "bfs"][c] for q in qs)
    w = harness.cut_window(loop.queries, 0.3)
    assert w.t_close - w.t_open >= 0.3 and w.queries


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s18.mix",
         "--seed", "2147483660", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_every_seed_sends_the_same_batches_of_roots():
    g = harness.generate_graph(harness.Cell(
        REPO, "t", 1, tiny_kronecker(), {}, [], []), 0)
    a = harness.draw_roots(g, 20, 2 ** 31 + 1, 4)
    b = harness.draw_roots(g, 20, 2 ** 31 + 2, 4)
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)
    whole = a.size - a.size % 4
    np.testing.assert_array_equal(np.sort(a[:whole].reshape(-1, 4), axis=1),
                                  np.sort(b[:whole].reshape(-1, 4), axis=1))
    np.testing.assert_array_equal(a, harness.draw_roots(g, 20, 2 ** 31 + 1, 4))
