"""One run of one cell: set up the service, drive it with a closed loop
of clients, cut the measured window, read the metrics and check the
answers.

Set-up (``setup_s``) runs from process start to the window's opening:
JAX start-up, the graph, ``add_graph`` (partition and upload),
``warm()`` of every kernel the traffic sends at the batch sizes its
clients make, and the wait for the first answer. The window opens at
the first answer any client holds and closes at the first answer of
the same kernel held ``--seconds`` or more later; the queries answered
in between, the opening one included and the closing one not, are the
window's. Closing on the opening kernel makes a window of a mix hold
whole rounds of it: as many answers of each kernel counted as were
computed inside the window.

Every time the harness reads is a client-side ``perf_counter``, taken
when a client holds its answer; nothing in it depends on how the
service forms or dispatches batches.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

GID = "bench"
BENCH = Path(__file__).resolve().parent


class NoDevice(RuntimeError):
    """JAX finds no accelerator of a known kind, or too few of them."""


# ---------------------------------------------------------------------------
# cells, found by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def bench(self) -> Path:
        return self.root / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> Cell:
    """The ``workloads`` entry named ``workload`` in
    ``<root>/BENCHMARK.json``, with its configuration, traffic mix and
    the metrics it reports."""
    spec = load_json(root / "BENCHMARK.json")
    try:
        w = next(w for w in spec["workloads"] if w["name"] == workload)
    except StopIteration:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")

    def reported(m):
        return workload in m.get("workloads", [workload])

    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=load_json(root / "bench" / "configs"
                                 / f"{w['config']}.json"),
                traffic=load_json(root / "bench" / "traffic"
                                  / f"{w['traffic']}.json"),
                end_to_end=[m for m in spec["end_to_end"] if reported(m)],
                per_layer=[m for m in spec["per_layer"] if reported(m)])


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate_graph(cell: Cell, seed: int):
    gen = load_module(cell.bench / "graphs" / f"{cell.config['graph']}.py")
    return gen.generate(cell.config, seed)


# ---------------------------------------------------------------------------
# device and compile cache
# ---------------------------------------------------------------------------

def open_devices(chips: int, *, require_tpu: bool = True):
    """The first ``chips`` JAX devices; :class:`NoDevice` unless they
    are TPUs of a kind ``bench/peaks.json`` knows."""
    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if require_tpu:
        if platform != "tpu":
            raise NoDevice(f"no TPU found (JAX platform is {platform!r})")
        if kind not in load_json(BENCH / "peaks.json")["devices"]:
            raise NoDevice(f"device kind {kind!r} is not in "
                           "bench/peaks.json")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} devices, JAX sees "
                       f"{len(devices)}")
    return devices[:chips]


def place_compile_cache(root: Path) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself),
    else the fixed ``bench/.cache/jax`` of this checkout: the path is
    part of the cache key, so it must not move between runs. Every
    program is cached, however quickly it compiled, so that set-up is
    the same work on every run after the first."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / "bench" / ".cache" / "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# the closed loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Query:
    client: int
    kernel: str
    root: int
    t_submit: float
    t_done: float = math.nan   # when the client holds the answer
    supersteps: int = 0
    fut: Optional[cf.Future] = None
    answer: Optional[Dict[str, np.ndarray]] = None
    error: Optional[BaseException] = None
    cancelled: bool = False    # still queued when the window closed


class ClosedLoop:
    """``len(kernels)`` clients, one thread each with one query in
    flight: client ``c`` sends kernel ``kernels[c]`` from roots
    ``roots[c]``, ``roots[c + n]``, ``roots[c + 2n]``, ... (``n``
    clients), and sends again as soon as it holds the answer, until the
    window closes. Clients start in client order, each once the one
    before it has begun its first submit, so the classes queue in the
    same order on every run.

    ``on_open`` and ``on_close`` run in the client thread that first
    holds an answer, and in the first that holds one of the same kernel
    ``seconds`` later.
    After the close, queries the service has not started are withdrawn
    and the loop waits for those it has."""

    def __init__(self, svc, kernels: List[str], roots: np.ndarray,
                 deadline_ms: float, seconds: float,
                 on_open=None, on_close=None):
        self.svc = svc
        self.kernels = kernels
        self.roots = roots
        self.deadline_ms = deadline_ms
        self.seconds = seconds
        self.on_open, self.on_close = on_open, on_close
        self.queries: List[Query] = []
        self._lock = threading.Lock()
        self._first: Optional[Query] = None
        self.closed = threading.Event()

    def _root(self, client: int, k: int) -> int:
        n = len(self.kernels)
        return int(self.roots[(k * n + client) % self.roots.size])

    def _answered(self, q: Query) -> None:
        with self._lock:
            if self._first is None:
                self._first = q
                hook = self.on_open
            elif (closes(q, self._first, self.seconds)
                  and not self.closed.is_set()):
                self.closed.set()
                hook = self.on_close
            else:
                hook = None
        if hook is not None:
            hook()

    def _client(self, c: int, started: threading.Event) -> None:
        try:
            self._send_until_closed(c, self.kernels[c], started)
        finally:
            started.set()

    def _send_until_closed(self, c: int, kernel: str,
                           started: threading.Event) -> None:
        import jax
        from repro.service import QueryRequest
        k = 0
        while not self.closed.is_set():
            req = QueryRequest(GID, kernel, {"root": self._root(c, k)},
                               deadline_ms=self.deadline_ms)
            k += 1
            q = Query(c, kernel, req.query_kwargs["root"],
                      time.perf_counter())
            with self._lock:
                self.queries.append(q)
            started.set()
            try:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    q.fut = self.svc.submit(req)
                with jax.profiler.TraceAnnotation("bench.wait_answer"):
                    res = q.fut.result()
                q.supersteps = int(res.supersteps)
                q.answer = {k_: res.state[k_] for k_ in ("parent", "dist")
                            if k_ in res.state}
            except cf.CancelledError:
                q.cancelled = True
                return
            except Exception as e:  # noqa: BLE001 -- counted as failed
                q.error = e
            q.t_done = time.perf_counter()
            self._answered(q)

    def run(self, timeout: float) -> None:
        """Start the clients, wait for the close, withdraw what the
        service has not started and wait for the rest, ``timeout``
        seconds at most in all."""
        deadline = time.monotonic() + timeout
        threads = []
        for c in range(len(self.kernels)):
            started = threading.Event()
            t = threading.Thread(target=self._client, args=(c, started),
                                 name=f"bench-client-{c}", daemon=True)
            t.start()
            started.wait()
            threads.append(t)
        self.closed.wait(max(0.0, deadline - time.monotonic()))
        self.closed.set()
        while threads and time.monotonic() < deadline:
            with self._lock:
                pending = [q.fut for q in self.queries if q.fut is not None]
            for f in pending:
                f.cancel()      # a no-op once the service started it
            threads[0].join(timeout=0.05)
            threads = [t for t in threads if t.is_alive()]


@dataclasses.dataclass
class Window:
    """The measured window and the queries answered in it."""
    t_open: float
    t_close: float
    queries: List[Query]

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def closes(q: Query, first: Query, seconds: float) -> bool:
    """Whether answer ``q`` closes a window that ``first`` opened."""
    return q.kernel == first.kernel and q.t_done >= first.t_done + seconds


def cut_window(queries: List[Query], seconds: float) -> Window:
    """Opens at the first answer, closes at the first answer of the same
    kernel at or after ``seconds`` later; holds the answers in
    ``[open, close)``."""
    done = sorted((q for q in queries if not math.isnan(q.t_done)),
                  key=lambda q: q.t_done)
    if not done:
        raise RuntimeError("no query was answered")
    first = done[0]
    later = [q.t_done for q in done if closes(q, first, seconds)]
    if not later:
        raise RuntimeError(f"no {first.kernel} answer came {seconds} s "
                           "after the first")
    t_open, t_close = first.t_done, later[0]
    return Window(t_open=t_open, t_close=t_close,
                  queries=[q for q in queries
                           if t_open <= q.t_done < t_close])


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What metric readers (``bench/metrics/<name>.py``) read."""
    cell: Cell
    seed: int
    t_start: float
    graph: object
    loop: ClosedLoop
    svc: object
    # stats_snapshot() at the loop's "start", the window's "open" and
    # "close", and the loop's "end"
    counters: Dict[str, dict]
    t_loop: tuple = (math.nan, math.nan)   # loop start and end
    window: Optional[Window] = None
    events: list = dataclasses.field(default_factory=list)  # TraceBus
    xplane: Optional[tuple] = None  # (trace file, clock mark), traced runs
    device: object = None          # trace.WindowReduction of the window
    loop_device: object = None     # ... and of the whole loop
    _comp_edges: Optional[np.ndarray] = None

    @property
    def setup_s(self) -> float:
        return self.window.t_open - self.t_start

    @property
    def component_edges(self) -> np.ndarray:
        if self._comp_edges is None:
            self._comp_edges = self.graph.component_edges()
        return self._comp_edges

    def counter(self, name: str, lo: str, hi: str) -> Optional[float]:
        """Change of the service's ``stats_snapshot()[name]`` from the
        snapshot ``lo`` to ``hi``; None when one was not taken."""
        if lo not in self.counters or hi not in self.counters:
            return None
        return self.counters[hi][name] - self.counters[lo][name]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def draw_roots(graph, structure_seed: int, seed: int,
               group: int) -> np.ndarray:
    """Every vertex with an edge, in an order drawn from
    ``structure_seed`` (the Graph500 search-key rule), then each
    consecutive ``group`` of it reordered by the run's seed. Clients take
    the roots in turn, so no root repeats before all have been used once,
    and with ``group`` the batch size, every seed sends the same roots in
    the same batches, each batch's in another order: the work of a run
    does not change with the seed, which places the roots in the lanes."""
    order = np.random.default_rng([structure_seed, 0x5EA4C4]).permutation(
        np.flatnonzero(graph.degrees() > 0))
    whole = order.size - order.size % group
    blocks = np.random.default_rng([seed, 0x5EA4C5]).permuted(
        order[:whole].reshape(-1, group), axis=1)
    return np.concatenate([blocks.ravel(), order[whole:]])


def warm_sizes(clients: int, max_batch: int) -> Optional[List[int]]:
    """The batch sizes a closed loop of ``clients`` of one kernel makes:
    only full batches when ``max_batch`` divides ``clients``, since a
    client sends again within milliseconds of its answer and every
    deadline lies far beyond that; else None, warm()'s own buckets."""
    return [max_batch] if clients % max_batch == 0 else None


def batch_size(cell: Cell) -> int:
    """The service's ``max_batch`` as the configuration deploys it."""
    import inspect
    from repro.service import GraphQueryService
    default = inspect.signature(GraphQueryService).parameters["max_batch"]
    return int(cell.config.get("service", {}).get("max_batch",
                                                  default.default))


def serve(cell: Cell, seed: int, seconds: float, t_start: float,
          trace_dir: Optional[Path] = None,
          timeout: float = 600.0) -> Run:
    """Set up the service for ``cell``, run the closed loop and cut the
    window. With ``trace_dir`` the loop runs under the JAX profiler.
    The service keeps its defaults but for what the configuration
    states as a deployment setting (``service``)."""
    import jax
    from repro.core.graph import Graph
    from repro.service import GraphQueryService
    cfg, traffic = cell.config, cell.traffic

    t = time.perf_counter()
    graph = generate_graph(cell, seed)
    log(f"[setup] graph {cfg['graph']}: {graph.num_vertices} vertices, "
        f"{graph.num_undirected} undirected edges in "
        f"{time.perf_counter() - t:.3f} s")
    svc = GraphQueryService(**cfg.get("service", {}))
    t = time.perf_counter()
    src, dst, w = graph.directed()
    svc.add_graph(GID, Graph(graph.num_vertices, src, dst, w))
    log(f"[setup] add_graph {time.perf_counter() - t:.3f} s")
    kernels = [c["kernel"] for c in traffic["clients"]
               for _ in range(int(c["count"]))]
    for k in dict.fromkeys(kernels):
        t = time.perf_counter()
        sizes = warm_sizes(kernels.count(k), svc.max_batch)
        svc.warm(GID, k, batch_sizes=sizes)
        log(f"[setup] warm {k} batches {sizes or 'default'} "
            f"{time.perf_counter() - t:.3f} s")

    roots = draw_roots(graph, int(cfg["structure_seed"]), seed,
                       svc.max_batch)
    if roots.size <= svc.result_cache_size:
        # a root repeats only after every other one has been sent, so
        # with more roots than the result cache holds none is a hit
        raise ValueError(f"{roots.size} roots do not outrun the service's "
                         f"result cache of {svc.result_cache_size}")
    counters: Dict[str, dict] = {}

    def snapshot(name):
        return lambda: counters.__setitem__(name, svc.stats_snapshot())

    loop = ClosedLoop(svc, kernels, roots,
                      deadline_ms=float(traffic["deadline_ms"]),
                      seconds=seconds, on_open=snapshot("open"),
                      on_close=snapshot("close"))
    svc.start()
    run = Run(cell=cell, seed=seed, t_start=t_start, graph=graph, loop=loop,
              svc=svc, counters=counters)
    try:
        snapshot("start")()
        t0 = time.perf_counter()
        if trace_dir is None:
            loop.run(timeout)
        else:
            from bench import trace as tr
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=tr.profile_options())
            with jax.profiler.TraceAnnotation(tr.CLOCK_MARK):
                t_mark = time.perf_counter()
            try:
                with tr.host_spans():
                    loop.run(timeout)
            finally:
                jax.profiler.stop_trace()
            run.xplane = (tr.find_xplane(trace_dir), t_mark)
        run.t_loop = (t0, time.perf_counter())
        snapshot("end")()
        run.window = cut_window(loop.queries, seconds)
    except BaseException:
        finish(run)
        raise
    return run


def finish(run: Run) -> None:
    """Stop the service, mark what never answered, and read what needs
    the whole run: the service's events and the trace."""
    loop = run.loop
    loop.closed.set()
    run.svc.stop()
    never = 0
    for q in loop.queries:
        if not q.cancelled and math.isnan(q.t_done) and q.error is None:
            q.error = TimeoutError("never answered")
            never += 1
    if never:
        log(f"[run] {never} queries never answered")
    run.events = run.svc.trace_snapshot()
    if run.xplane is not None and run.window is not None:
        from bench import trace as tr
        dev, host = tr.load(run.xplane[0])
        off = tr.clock_offset_ns(host, run.xplane[1])
        planes = sorted(dev)[:run.cell.chips]
        for attr, (lo, hi) in (
                ("device", (run.window.t_open, run.window.t_close)),
                ("loop_device", run.t_loop)):
            setattr(run, attr, tr.reduce_window(
                dev, host, lo * 1e9 + off, hi * 1e9 + off, planes))


def check_answers(run: Run) -> Dict[str, dict]:
    """Compare a sample of the window's answers, drawn from the seed
    (``check_per_kernel`` of each kernel, 0 for all), with the host
    reference. Returns the compared numbers; ``missing`` is the
    caller's to fill in once every query has answered or timed out."""
    from bench.check import compare
    from bench.reference import HostReference
    cfg = run.cell.config
    n = int(cfg.get("check_per_kernel", 0))
    rng = np.random.default_rng([run.seed, 0xC4EC])
    sample = []
    for k in dict.fromkeys(run.loop.kernels):
        qs = [q for q in run.window.queries
              if q.kernel == k and q.answer is not None]
        if n and len(qs) > n:
            qs = [qs[i] for i in sorted(rng.choice(len(qs), n,
                                                   replace=False))]
        sample += qs
    t = time.perf_counter()
    _, numbers = compare(HostReference(run.graph),
                         [(q.kernel, q.root, q.answer) for q in sample],
                         cfg.get("limits", {}), missing=0)
    log(f"[check] {len(sample)} answers compared with the host reference "
        f"in {time.perf_counter() - t:.3f} s")
    return numbers


def read_metrics(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    """Each metric from its reader ``bench/metrics/<name>.py``; a reader
    that finds nothing to read returns None and the metric is left
    out."""
    out = {}
    for m in metrics:
        value = load_module(run.cell.bench / "metrics"
                            / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def answer_groups(queries: List[Query], gap: float = 0.5) -> List[tuple]:
    """(first answer time, count) of each run of answers held less than
    ``gap`` seconds apart: the batches, as the clients saw them."""
    groups: List[list] = []
    for t in sorted(q.t_done for q in queries if not math.isnan(q.t_done)):
        if groups and t - groups[-1][2] < gap:
            groups[-1][1] += 1
            groups[-1][2] = t
        else:
            groups.append([t, 1, t])
    return [(t, n) for t, n, _ in groups]


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, require_tpu: bool = True
             ) -> dict:
    """The whole run; returns the result line's object. Raises
    :class:`NoDevice` before any work when the device is wrong."""
    cell = load_cell(root, workload)
    devices = open_devices(cell.chips, require_tpu=require_tpu)
    cache = place_compile_cache(root)
    log(f"[setup] {len(devices)} x {devices[0].device_kind}; compile "
        f"cache {cache}")
    trace_dir = cell.bench / ".cache" / "trace" if trace else None
    run = serve(cell, seed, seconds, t_start, trace_dir)
    finish(run)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes(devices)}
    numbers = check_answers(run)
    w = run.window
    log(f"[run] set-up {run.setup_s:.3f} s; window {w.seconds:.3f} s, "
        f"{len(w.queries)} queries; loop {run.t_loop[1] - run.t_loop[0]:.3f}"
        f" s, {run.counter('supersteps_total', 'start', 'end')} supersteps")
    log("[run] answers (s after loop start, count): " + ", ".join(
        f"{t - run.t_loop[0]:.2f} x{n}"
        for t, n in answer_groups(run.loop.queries)))
    for k in dict.fromkeys(run.loop.kernels):
        lat = [q.t_done - q.t_submit for q in w.queries if q.kernel == k]
        log(f"[run] {k}: {len(lat)} answers in the window, latency median "
            f"{np.median(lat) if lat else math.nan:.3f} s, max "
            f"{max(lat, default=math.nan):.3f} s")
    sent = [q for q in run.loop.queries if not q.cancelled]
    failed = sum(q.error is not None for q in sent)
    result = {"correct": False, "attempted": len(sent), "failed": failed}
    result["metrics"] = read_metrics(
        run, cell.per_layer if trace else cell.end_to_end)
    if trace and run.device is None and require_tpu:
        raise RuntimeError("the trace holds no device operation")
    if trace and run.device is not None:
        device["busy_s"] = run.device.busy_s
        device["window_s"] = run.device.window_s
    result["device"] = device
    if trace and run.device is not None:
        result["breakdown"] = {"device_ops": run.device.device_ops,
                               "idle_gaps": run.device.idle_gaps}
    numbers["missing"]["value"] = failed
    result["correct"] = all(n["value"] <= n["limit"]
                            for n in numbers.values())
    result["checks"] = numbers
    for name, n in numbers.items():
        ok = "ok" if n["value"] <= n["limit"] else "FAILED"
        log(f"check {name} {n['value']!r} limit {n['limit']!r} {ok}")
    return result
