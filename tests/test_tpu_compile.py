"""The served path's Pallas kernels and search steps compile for a TPU v5e.

Compiles against a described (not attached) ``v5e:2x2`` topology, so the
TPU compiler refuses here what it would refuse on the chip: tile-unaligned
blocks and DMAs, unsupported ops, VMEM overruns. Nothing runs. The topology
is described inside a fixture, never at import, and every test that needs
it lives in this one file. Code that asks ``jax.default_backend()`` still
sees the CPU here, so the tests steer ``ops._on_tpu`` to take the TPU
branch (compiled kernels, never interpret mode).

The last test drives ``chip_smoke.py``'s phases at a tiny size on the CPU.
"""
from __future__ import annotations

import importlib.util
import itertools
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.beam_merge import beam_merge_pallas
from repro.kernels.filter_dist import (
    filter_dist_gather_packed_pallas,
    filter_dist_gather_pallas,
)
from repro.kernels.layout import LANE

N = 65536          # rows of the HBM tables (one shard of the deployment)
E = 96             # labeled degree of the deployment


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Take the TPU branch of ``repro.kernels.ops`` (backend is the CPU)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _spec(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)


def _table(S, d, dtype):
    """The vector table in the kernels' row layout: f32 rows, or int8
    values packed four to an int32 word."""
    if dtype == "f32":
        return S((N, 1, -(-d // LANE) * LANE), jnp.float32)
    return S((N, 1, -(-d // (4 * LANE)) * LANE), jnp.int32)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


GATHER_CASES = list(itertools.product((128, 768), ("f32", "int8"), (1, 4),
                                      (8, 256)))


@pytest.mark.parametrize("d,dtype,M,B", GATHER_CASES)
def test_packed_gather_kernel_compiles(one_chip, d, dtype, M, B):
    S = _spec(one_chip)
    C = M * E
    text = _compiled_text(
        filter_dist_gather_packed_pallas,
        _table(S, d, dtype), S((N, 1, 2 * LANE), jnp.int32),
        S((B, d), jnp.float32), S((B, M), jnp.int32), S((B, C), jnp.int32),
        S((B, 2), jnp.int32), S((B, C), jnp.float32), S((B, C), jnp.uint32),
        S((B, C), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("deg", [32, 48, 64])
def test_packed_gather_kernel_compiles_below_one_lane_tile(one_chip, deg):
    """Degrees whose two label words share one 128-lane row: word 1 starts
    inside the tile and the kernel rotates it down."""
    S = _spec(one_chip)
    B, M, d = 8, 4, 768
    C = M * deg
    text = _compiled_text(
        filter_dist_gather_packed_pallas,
        _table(S, d, "f32"), S((N, 1, LANE), jnp.int32),
        S((B, d), jnp.float32), S((B, M), jnp.int32), S((B, C), jnp.int32),
        S((B, 2), jnp.int32), S((B, C), jnp.float32), S((B, C), jnp.uint32),
        S((B, C), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d,dtype,M,B", GATHER_CASES)
def test_gather_kernel_compiles(one_chip, d, dtype, M, B):
    S = _spec(one_chip)
    C = M * E
    text = _compiled_text(
        filter_dist_gather_pallas,
        _table(S, d, dtype), S((B, d), jnp.float32), S((B, C), jnp.int32),
        S((B, C, 4), jnp.int32), S((B, 2), jnp.int32),
        S((B, C), jnp.float32), S((B, C), jnp.uint32),
        S((B, C), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("C", [96, 128, 384])
def test_beam_merge_kernel_compiles(one_chip, C):
    S = _spec(one_chip)
    B, L = 8, 64
    text = _compiled_text(
        lambda *a: beam_merge_pallas(*a, n=N),
        S((B, L), jnp.float32), S((B, L), jnp.int32), S((B, L), jnp.bool_),
        S((B, C), jnp.float32), S((B, C), jnp.int32))
    assert "tpu_custom_call" in text


def test_planned_streaming_step_compiles(one_chip, on_tpu):
    """The served step of ``StreamingIndex.search(plan="auto")`` at the
    deployment widths (small n) holds the kernels, not the oracles."""
    from repro.stream.search import planned_streaming_search_core

    S = _spec(one_chip)
    n, d, B, C, V = 1024, 768, 8, 64, 256
    lowered = planned_streaming_search_core.lower(
        S((n, 1, d), jnp.float32), S((n, E), jnp.int32),
        S((n, 1, 2 * LANE), jnp.int32), S((n,), jnp.bool_),
        S((n,), jnp.int32), S((C, d), jnp.float32), S((C, 4), jnp.int32),
        S((C,), jnp.int32), S((C,), jnp.int32), S((B, d), jnp.float32),
        S((B, 2), jnp.int32), S((B,), jnp.int32), S((B,), jnp.int32),
        S((B, V), jnp.int32), S((B,), jnp.int32), S((B, 2), jnp.int32),
        k=10, beam=64, wide_beam=128, max_iters=128, wide_max_iters=256,
        use_ref=None, fused=True, wide_expand=2, norms=S((n,), jnp.float32))
    text = lowered.compile().as_text()
    # packed superkernel + bitonic merge (graph and wide searches), the
    # gather kernel of the brute-valid scan and of the delta tier
    assert text.count("tpu_custom_call") >= 5


def test_served_step_keeps_the_d128_table_in_vmem(one_chip, on_tpu):
    """At one shard of ``sift128-overlap`` (65,536 × 128 f32, 32 MB) the
    compiler places the vector table in VMEM (memory space S(1)) for both
    search loops' packed gather kernels, whose per-row DMAs then read it
    from there: 1.5× faster per call on a TPU v5e than from HBM. Keeping
    the visited bitmap alive past the loops once moved it out."""
    import re

    from repro.stream.search import planned_streaming_search_core

    S = _spec(one_chip)
    d, B, C, V = 128, 256, 1024, 256
    text = planned_streaming_search_core.lower(
        S((N, 1, d), jnp.float32), S((N, E), jnp.int32),
        S((N, 1, 2 * LANE), jnp.int32), S((N,), jnp.bool_),
        S((N,), jnp.int32), S((C, d), jnp.float32), S((C, 4), jnp.int32),
        S((C,), jnp.int32), S((C,), jnp.int32), S((B, d), jnp.float32),
        S((B, 2), jnp.int32), S((B,), jnp.int32), S((B,), jnp.int32),
        S((B, V), jnp.int32), S((B,), jnp.int32), S((B, 2), jnp.int32),
        k=10, beam=64, wide_beam=128, max_iters=128, wide_max_iters=256,
        use_ref=None, fused=True, wide_expand=2,
        norms=S((N,), jnp.float32)).compile().as_text()
    shapes = dict(re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ", text,
                             re.M))
    calls = re.findall(r"%filter_dist_gather_packed_pallas\.\d+ = \S+ "
                       r"custom-call\(([^)]*)\)", text)
    assert len(calls) == 2
    for operands in calls:
        table = operands.split(",")[2].strip().lstrip("%")
        assert shapes[table].startswith(f"f32[{N},1,{d}]"), shapes[table]
        assert "S(1)" in shapes[table], shapes[table]


def test_build_wave_search_compiles(one_chip, on_tpu):
    """The batched constructor's label-ignoring wave search (a logical
    ``[n, d]`` table, converted to rows in-graph) at the deployment's
    wave width and d = 768."""
    from repro.search.batched import broad_batched_search

    S = _spec(one_chip)
    n, d, W, Z = 4096, 768, 512, 64
    text = _compiled_text(
        lambda tab, nrm, nbr, q, ep: broad_batched_search(
            tab, nrm, nbr, q, ep, k=Z, beam=Z, expand=4),
        S((n, d), jnp.float32), S((n,), jnp.float32), S((n, Z), jnp.int32),
        S((W, d), jnp.float32), S((W,), jnp.int32))
    assert "tpu_custom_call" in text


def test_sharded_planned_step_compiles_on_four_chips(topo, on_tpu):
    """``serve_batch``'s planned step over a 1x4 mesh, one shard per chip."""
    from repro.exec import default_planner_config
    from repro.serve.distributed import make_planned_serving_step

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    S4, n_l, d, B, V, ux = 4, 1024, 768, 8, 256, 512

    def sh(shape, dt, spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    m, q, pq = P("model"), P(("data",)), P("model", ("data",))
    step = make_planned_serving_step(mesh, "containment", k=10, beam=64,
                                     config=default_planner_config())
    compiled = step.lower(
        sh((S4, n_l, 1, d), jnp.float32, m), sh((S4, n_l, E), jnp.int32, m),
        sh((S4, n_l, 1, 2 * LANE), jnp.int32, m),
        sh((S4, n_l), jnp.float32, m), sh((S4, ux), jnp.float32, m),
        sh((S4, ux), jnp.float32, m), sh((S4,), jnp.int32, m),
        sh((S4, ux), jnp.int32, m), sh((S4, ux), jnp.int32, m),
        sh((B, d), jnp.float32, q), sh((B,), jnp.float32, q),
        sh((B,), jnp.float32, q), sh((S4, B), jnp.int32, pq),
        sh((S4, B, V), jnp.int32, pq)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    # one device holds one shard: a quarter of the stacked table
    assert (compiled.memory_analysis().argument_size_in_bytes
            < S4 * n_l * d * 4 / 2)


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_on_cpu(capsys):
    """The one-chip phases of ``chip_smoke.py`` at a tiny size: build,
    serve (both a brute-valid and a graph plan), mutate, compare against
    the ground truth and the reference, inspect the served step."""
    cs = _chip_smoke()
    z = cs.Sizes(n=3000, dim=16, degree=32, wave=64, batch=32,
                 delta_capacity=64, n_insert=48, n_delete=16, min_recall=0.5)
    cs.one_chip(z, 0, cs.CompileClock(), expect_kernels=False)
    out = capsys.readouterr().out
    for phase in ("build", "serve", "compare", "delta", "verify", "memory"):
        assert f"phase={phase}" in out
    assert "packed_labels=True" in out
