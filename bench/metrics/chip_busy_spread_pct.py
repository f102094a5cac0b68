"""Sharding: how unevenly the chips of the cell work, (max - min) /
mean of the chips' device busy time over the whole traced loop, in
percent (the busy times of ``mesh_superstep_ms``). A shard with more
edges than the others keeps its chip busy while the rest wait for the
superstep's vote."""
from pathlib import Path

from bench.harness import load_module


def read(run):
    busy = load_module(Path(__file__).with_name(
        "mesh_superstep_ms.py")).chip_busy_s(run)
    if not busy or sum(busy) <= 0:
        return None
    return (max(busy) - min(busy)) / (sum(busy) / len(busy)) * 100.0
