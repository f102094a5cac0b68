"""Data pipeline determinism + sharding rule unit tests."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding as SH
from repro.data.pipeline import DataConfig, SyntheticTokens


def test_data_determinism_and_restart_safety():
    cfg = DataConfig(vocab=1000, global_batch=8, seq_len=64)
    a = SyntheticTokens(cfg)
    b = SyntheticTokens(cfg)  # a "restarted" pipeline
    for step in (0, 5, 17):
        ba, bb = a.batch(step), b.batch(step)
        np.testing.assert_array_equal(ba["tokens"], bb["tokens"])
        np.testing.assert_array_equal(ba["labels"], bb["labels"])
    assert not np.array_equal(a.batch(0)["tokens"], a.batch(1)["tokens"])


def test_data_label_shift():
    cfg = DataConfig(vocab=1000, global_batch=2, seq_len=32)
    b = SyntheticTokens(cfg).batch(0)
    assert b["tokens"].shape == (2, 32)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_data_process_sharding():
    cfg = DataConfig(vocab=100, global_batch=8, seq_len=16)
    parts = [SyntheticTokens(cfg, process_index=i, process_count=4)
             .batch(3)["tokens"] for i in range(4)]
    assert all(p.shape == (2, 16) for p in parts)
    # different processes see different rows
    assert not np.array_equal(parts[0], parts[1])


def test_logical_to_spec_divisibility():
    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((1,), ("model",))
    # 'model' size 1: everything maps but is trivial; use the table only.
    spec = SH.logical_to_spec(mesh, ("batch", None, "vocab"), (8, 4, 100))
    assert isinstance(spec, P)


def test_vocab_padding():
    from repro import configs
    cfg = configs.get("seamless-m4t-medium")
    assert cfg.vocab == 256206           # logical vocab: exact assignment
    assert cfg.vocab_padded % 2048 == 0  # physical table: TP-divisible
    assert cfg.vocab_padded >= cfg.vocab
    for name in configs.ARCH_IDS:
        c = configs.get(name)
        if name != "seamless-m4t-medium":
            assert c.vocab_padded == c.vocab  # others are already divisible


def test_arch_registry_complete():
    from repro import configs
    assert len(configs.ARCH_IDS) == 10
    for name in configs.ARCH_IDS:
        full = configs.get(name)
        red = configs.get(name, reduced=True)
        assert full.name == name
        assert red.n_layers <= full.n_layers
        assert red.d_model < full.d_model
        # reduced preserves the family and pattern structure
        assert red.family == full.family
        assert len(red.block_pattern) == len(full.block_pattern)
        assert [k.mixer for k in red.block_pattern] == \
               [k.mixer for k in full.block_pattern]


def test_assigned_dimensions_exact():
    """The exact assignment table (spot-check every arch)."""
    from repro import configs
    expect = {
        "xlstm-350m": (24, 1024, 4, 0, 50304),
        "seamless-m4t-medium": (24, 1024, 16, 4096, 256206),
        "qwen3-4b": (36, 2560, 32, 9728, 151936),
        "qwen2-72b": (80, 8192, 64, 29568, 152064),
        "gemma3-27b": (62, 5376, 32, 21504, 262144),
        "minitron-4b": (32, 3072, 24, 9216, 256000),
        "internvl2-76b": (80, 8192, 64, 28672, 128256),
        "recurrentgemma-9b": (38, 4096, 16, 12288, 256000),
        "deepseek-moe-16b": (28, 2048, 16, 1408, 102400),
        "deepseek-v2-236b": (60, 5120, 128, 1536, 102400),
    }
    for name, (L_, d, h, ff, v) in expect.items():
        c = configs.get(name)
        n_layers = c.n_layers if c.family != "encdec" else c.n_enc + c.n_dec
        assert n_layers == L_, name
        assert c.d_model == d, name
        assert c.n_heads == h, name
        assert c.d_ff == ff, name
        assert c.vocab == v, name
    # MoE extras
    dm = configs.get("deepseek-moe-16b").moe
    assert (dm.n_routed, dm.n_shared, dm.topk) == (64, 2, 6)
    dv = configs.get("deepseek-v2-236b")
    assert (dv.moe.n_routed, dv.moe.n_shared, dv.moe.topk) == (160, 2, 6)
    assert dv.mla.kv_lora == 512
