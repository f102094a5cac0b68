"""Helpers shared by the benchmark's own tests: tiny cells in a temp
copy of ``bench/``, run on the CPU."""
import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]


def tiny_root(tmp: Path, *, configs=None, traffic=None, workloads=(),
              metrics=None) -> Path:
    """A checkout-shaped directory at ``tmp``: a copy of ``bench/`` and
    a ``BENCHMARK.json`` with ``workloads`` added, each reporting every
    per-layer metric. ``configs``/``traffic``/``metrics`` map names to
    the JSON (or, for metrics, the Python source) of new files."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"] += list(workloads)
    names = [w["name"] for w in workloads]
    for m in spec["per_layer"]:
        m["workloads"] += names
    for name, src in (metrics or {}).items():
        (tmp / "bench" / "metrics" / f"{name}.py").write_text(src)
    for name, cfg in (configs or {}).items():
        (tmp / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, t in (traffic or {}).items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(t))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def tiny_kronecker(scale=9, **over):
    """Scale 9 has more roots than the service's result cache holds."""
    cfg = json.loads((REPO / "bench/configs/g500-s18.json").read_text())
    # a sample of 16 answers per kernel: a fault in one lane of two
    # escapes it with probability 2**-16
    cfg.update(scale=scale, service={"max_batch": 2}, check_per_kernel=16,
               **over)
    return cfg


TINY_MIX = {"loop": "closed", "deadline_ms": 60000,
            "clients": [{"kernel": "bfs", "count": 2},
                        {"kernel": "sssp", "count": 2}]}


@pytest.fixture
def tiny_mix_root(tmp_path):
    return tiny_root(
        tmp_path, configs={"tiny": tiny_kronecker()},
        traffic={"tinymix": TINY_MIX},
        workloads=[{"name": "tiny.mix", "config": "tiny",
                    "traffic": "tinymix", "chips": 1, "why": "test"}])
