"""Compile the four enrichment kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed without a chip, lowers
each kernel at the widths chip_smoke.py drives on the chip, with the
package's x64 mode on.  This is what interpret mode cannot show: Mosaic
refuses 64-bit types, blocks that cut the hardware tiling, and kernels
that overrun fast memory.  The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only
the worker given this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro  # noqa: F401  (turns the package's x64 mode on)
from repro.kernels.hash_probe.kernel import sorted_probe_pallas
from repro.kernels.segment_reduce.kernel import segment_sum_pallas
from repro.kernels.segment_topk.kernel import segment_topk_pallas
from repro.kernels.spatial_join.kernel import radius_join_pallas

PROBE_ROWS = 8192                 # bucket of the paper's 16X batch (6,720)
SUSPICIOUS_NAMES = 1_001_024      # 1M keys + upsert headroom
MONUMENTS = 51_024                # 50K + upsert headroom
PERSONS_BUCKET = 1 << 20          # 1M persons + headroom, bucketed
Q6_SEGMENTS = 512 * 32 + 1        # trimmed districts x ethnicities + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *args):
    """Compile ``fn`` for the described chip at (shape, dtype) ``args``;
    the chip's compiler raises what it would refuse on the device."""
    assert jax.config.jax_enable_x64
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_hash_probe_compiles_at_suspicious_names_width(one_chip):
    _compile(sorted_probe_pallas, one_chip,
             ((PROBE_ROWS,), jnp.int64), ((SUSPICIOUS_NAMES,), jnp.int64))


@pytest.mark.parametrize("refs,k", [(MONUMENTS, 8), (10_240, 3)])
def test_spatial_join_compiles_at_paper_widths(one_chip, refs, k):
    f32 = jnp.float32
    _compile(lambda px, py, rx, ry, valid: radius_join_pallas(
                 px, py, rx, ry, 1.5, k, valid),
             one_chip, ((PROBE_ROWS,), f32), ((PROBE_ROWS,), f32),
             ((refs,), f32), ((refs,), f32), ((refs,), jnp.bool_))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_segment_sum_compiles_at_q6_segments(one_chip, dtype):
    _compile(lambda v, s: segment_sum_pallas(v, s, Q6_SEGMENTS), one_chip,
             ((PERSONS_BUCKET,), dtype), ((PERSONS_BUCKET,), jnp.int32))


def test_segment_topk_compiles_at_envelope_corner(one_chip):
    _compile(lambda v, s: segment_topk_pallas(v, s, 2048, 16), one_chip,
             ((1 << 18,), jnp.int32), ((1 << 18,), jnp.int32))


def test_group_count_within_radius_compiles_at_q5_width(one_chip):
    """Q5's 16-type radius count runs as jnp tiles on every path: the
    elementwise distances over (512, 50,176) tiles compile for the chip
    and fuse, leaving no tile in device memory."""
    from repro.core.enrich import ops
    facilities = 50_176                    # 50K, trimmed to 256s
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in (((6720, 2), jnp.float32),
                                  ((facilities, 2), jnp.float32),
                                  ((facilities,), jnp.int32),
                                  ((facilities,), jnp.bool_))]
    compiled = jax.jit(lambda p, r, g, v: ops.group_count_within_radius(
        p, r, g, 16, 3.0, v)).lower(*specs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * facilities


def _kernel_instructions(text):
    """The instructions of compiled HLO text that the benchmark's
    ``KERNELS`` patterns match, by kernel."""
    import re
    from bench.run import KERNELS
    found = set()
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[len("ROOT "):]
        for k, pat in KERNELS.items():
            if re.search(pat, line):
                found.add((k, line.split(" = ")[0]))
    return found


def test_fused_stage_scopes_leave_the_kernel_instructions_as_they_were(
        one_chip, monkeypatch):
    """A fused chain traces each stage under ``jax.named_scope``: the
    device profile's op names gain the stage, and the instructions the
    benchmark's roofline metrics find keep their names."""
    import contextlib
    import sys
    import numpy as np
    from repro.core import RefStore, records
    from repro.core.enrich import queries as Q
    from repro.kernels import dispatch_mode
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    for kernel in ("hash_probe", "segment_reduce"):
        # compile the Mosaic kernels, not their interpret-mode emulation
        monkeypatch.setattr(f"repro.kernels.{kernel}.ops.auto_interpret",
                            lambda i: bool(i))
    store = RefStore()
    Q.make_reference_tables(store, scale=0.002, seed=7)
    snaps = store.snapshot(("safety_levels", "persons", "facilities",
                            "district_areas", "average_incomes"))
    refs = {n: {k: np.asarray(v) for k, v in s.arrays.items()}
            for n, s in snaps.items()}
    batch = records.pad_batch(records.parse_json_lines(
        records.SyntheticTweets(seed=1).raw_lines(256)), 256)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def compiled():
        udf = Q.chain("q1q6", Q.Q1, Q.Q6)   # fresh functions: no jit cache
        with dispatch_mode("pallas"):
            state = on_chip(jax.eval_shape(udf.state_fn, refs))
            return (jax.jit(udf.state_fn).lower(on_chip(refs))
                    .compile().as_text()
                    + jax.jit(udf.apply_fn).lower(
                        on_chip(batch), state, on_chip(refs))
                    .compile().as_text())

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert 'op_name="jit(state_fn)/q6_tweet_context/' in scoped
    assert "q6_tweet_context/" not in plain
    kernels = _kernel_instructions(scoped)
    assert {k for k, _ in kernels} == {"hash_probe", "segment_sum"}
    assert kernels == _kernel_instructions(plain)
