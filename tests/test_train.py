"""Trainer substrate tests: optimizer, train step, checkpoint/restore,
fault-tolerant resume, gradient compression, and the IDEA-fed data plane."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import latest_step, restore, save
from repro.configs import smoke_config
from repro.core import FeedManager, RefStore
from repro.core.enrich import queries as Q
from repro.data.packing import StreamPacker
from repro.models import api
from repro.train import OptConfig, init_train_state, make_train_step
from repro.train import compression as C
from repro.train.data_feed import FeedDataSource
from repro.train.trainer import Trainer, TrainerConfig

CFG = smoke_config("deepseek-coder-33b")
OPT = OptConfig(lr=1e-3, warmup_steps=2, total_steps=50, weight_decay=0.01)


def _batches(n, b=2, s=32, seed=0, vocab=None):
    rng = np.random.default_rng(seed)
    v = vocab or CFG.vocab_size
    for _ in range(n):
        t = rng.integers(3, v, (b, s)).astype(np.int32)
        yield {"tokens": t, "targets": np.roll(t, -1, 1)}


# ---------------------------------------------------------------------------
# optimizer / step
# ---------------------------------------------------------------------------

def test_train_step_decreases_loss():
    state = init_train_state(CFG, OPT, jax.random.key(0))
    step = jax.jit(make_train_step(CFG, OPT))
    batch = next(_batches(1))
    losses = []
    for _ in range(20):
        state, m = step(state, batch)     # overfit one batch
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::5]
    assert int(state["step"]) == 20


def test_microbatch_accumulation_matches_full_batch():
    opt = OptConfig(lr=1e-3, warmup_steps=0, total_steps=10,
                    weight_decay=0.0, grad_clip=1e9)
    s1 = init_train_state(CFG, opt, jax.random.key(1))
    s2 = jax.tree.map(jnp.copy, s1)
    batch = next(_batches(1, b=4))
    step1 = jax.jit(make_train_step(CFG, opt, microbatches=1))
    step2 = jax.jit(make_train_step(CFG, opt, microbatches=2))
    s1, m1 = step1(s1, batch)
    s2, m2 = step2(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-5)


def test_factored_adam_state_is_smaller_and_trains():
    opt = OptConfig(lr=1e-3, factored_v=True, state_dtype="bfloat16",
                    warmup_steps=0, total_steps=50)
    state = init_train_state(CFG, opt, jax.random.key(0))
    full = sum(x.size for x in jax.tree.leaves(
        init_train_state(CFG, OPT, jax.random.key(0))["opt"]))
    fact = sum(x.size for x in jax.tree.leaves(state["opt"]))
    assert fact < 0.6 * full
    step = jax.jit(make_train_step(CFG, opt))
    batch = next(_batches(1))
    l0 = None
    for _ in range(15):
        state, m = step(state, batch)
        l0 = l0 or float(m["loss"])
    assert float(m["loss"]) < l0


# ---------------------------------------------------------------------------
# checkpoint / restore
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_retention(tmp_path):
    state = init_train_state(CFG, OPT, jax.random.key(0))
    for s in (1, 2, 3, 4):
        save(str(tmp_path), s, state, keep=2)
    assert latest_step(str(tmp_path)) == 4
    assert sorted(os.listdir(tmp_path)) == ["step_00000003",
                                            "step_00000004"]
    back = restore(str(tmp_path), state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_detects_corruption(tmp_path):
    state = {"w": jnp.arange(10, dtype=jnp.float32)}
    path = save(str(tmp_path), 1, state)
    leaf = os.path.join(path, "leaf_00000.npy")
    arr = np.load(leaf)
    arr[0] = 999
    np.save(leaf, arr)
    with pytest.raises(IOError, match="checksum"):
        restore(str(tmp_path), state)


def test_trainer_resumes_after_injected_failure(tmp_path):
    tcfg = TrainerConfig(steps=12, ckpt_dir=str(tmp_path), ckpt_every=4,
                         log_every=1, max_restarts=2)
    trainer = Trainer(CFG, OPT, tcfg)
    fails = {"left": 1}

    def fault_hook(step):
        if step == 6 and fails["left"]:
            fails["left"] -= 1
            raise RuntimeError("injected node failure")

    hist = trainer.run(_batches(100), fault_hook=fault_hook)
    assert trainer.restarts == 1
    assert int(trainer.state["step"]) == 12
    # resumed from step 4 checkpoint, not from scratch
    steps = [h["step"] for h in hist]
    assert 12 in steps


def test_trainer_fed_by_idea_pipeline():
    """End-to-end: IDEA feed -> tokenize UDF -> packer -> train steps."""
    store = RefStore()
    Q.make_reference_tables(store, scale=0.002, seed=7)
    mgr = FeedManager(store)
    src = FeedDataSource(mgr, vocab_size=CFG.vocab_size, seq_len=32,
                         batch_size=2, total_records=3000, frame_size=128,
                         safety_filter=True, num_partitions=2)
    tcfg = TrainerConfig(steps=5, log_every=1)
    trainer = Trainer(CFG, OPT, tcfg)
    hist = trainer.run(iter(src))
    assert int(trainer.state["step"]) == 5
    assert all(np.isfinite(h["loss"]) for h in hist)


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------

def test_packer_roundtrip_properties():
    packer = StreamPacker(seq_len=32, batch_size=2)
    docs = [[10, 11, 12], [20] * 40, [30, 31], [40, 41, 42, 43]] * 3
    batches = []
    for d in docs:
        out = packer.add(d)
        if out:
            batches.append(out)
    out = packer.flush()
    if out:
        batches.append(out)
    assert batches
    for b in batches:
        assert b["tokens"].shape == (2, 32)
        # loss mask covers exactly the segment-id-nonzero positions
        np.testing.assert_array_equal(b["loss_mask"] > 0,
                                      b["segment_ids"] > 0)
        # positions restart per segment
        for i in range(2):
            for seg in np.unique(b["segment_ids"][i]):
                if seg == 0:
                    continue
                pos = b["positions"][i][b["segment_ids"][i] == seg]
                np.testing.assert_array_equal(pos, np.arange(len(pos)))


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_compression_error_feedback_roundtrip():
    rng = np.random.default_rng(0)
    g = {"a": jnp.asarray(rng.normal(size=(300,)).astype(np.float32)),
         "b": jnp.asarray(rng.normal(size=(17, 5)).astype(np.float32))}
    err = C.init_error(g)
    comp, err2 = C.compress_tree(g, err)
    deq = C.decompress_tree(comp, g)
    # int8 quantization: ~1% relative error at block scale
    for k in g:
        rel = np.abs(np.asarray(deq[k] - g[k])).max() / \
            np.abs(np.asarray(g[k])).max()
        assert rel < 0.02, (k, rel)
        # error buffer holds exactly the residual
        np.testing.assert_allclose(np.asarray(err2[k]),
                                   np.asarray(g[k] - deq[k]), atol=1e-6)


def test_compressed_psum_matches_mean_multidevice():
    """8 fake devices: compressed DP mean ~= exact mean (subprocess so the
    512-device dry-run flag never leaks into this process)."""
    import subprocess
    import sys
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
import repro  # enables x64
from repro.train import compression as C

mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(8, 1024)).astype(np.float32))
err = jnp.zeros((8, 1024), jnp.float32)

def f(gl, el):
    red, e2 = C.psum_compressed({"g": gl[0]}, {"g": el[0]}, "data")
    return red["g"][None], e2["g"][None]

red, _ = jax.jit(shard_map(f, mesh=mesh,
                 in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data"))))(g, err)
exact = jnp.mean(g, axis=0)
got = np.asarray(red)[0]
rel = np.abs(got - np.asarray(exact)).max()
assert rel < 0.02, rel
print("OK", rel)
"""
    out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         env={**os.environ, "PYTHONPATH": "src"},
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
