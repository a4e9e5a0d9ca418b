"""The engine's device kernels compile for a TPU v5e (no chip needed).

The TPU compiler compiles for a described, unattached chip: these tests
lower the routing kernels and one keyed-running-sum step at the sizes
``chip_smoke.py`` runs (65,536 events, 400 key groups per operator,
20 nodes × 400 = 8,000 routing buckets plus a sentinel) and assert that
the chip's compiler accepts them — block shapes, fast-memory use and
Mosaic lowering included, none of which interpret mode checks.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

EVENTS = 65_536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _holds_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize(
    "n,num_keygroups",
    [(EVENTS, 400), (EVENTS, 16_000), (1_000, 13)],
)
def test_keygroup_partition_compiles(one_chip, n, num_keygroups):
    from repro.kernels.keygroup_partition.keygroup_partition import (
        keygroup_partition_pallas,
    )

    x = _spec(one_chip, (n,), jnp.int32)
    lowered = keygroup_partition_pallas.lower(x, x, num_keygroups=num_keygroups)
    assert _holds_kernel(lowered)


@pytest.mark.parametrize(
    "n,num_buckets",
    [(EVENTS, 400), (EVENTS, 8_000), (EVENTS, 8_001), (1_000, 13)],
)
def test_bucket_argsort_compiles(one_chip, n, num_buckets):
    from repro.kernels.radix_sort.radix_sort import bucket_argsort_pallas

    x = _spec(one_chip, (n,), jnp.int32)
    assert _holds_kernel(bucket_argsort_pallas.lower(x, num_buckets=num_buckets))


def test_bucket_argsort_compiles_inside_shard_map(topo):
    """The jit tier's mesh path sorts inside ``jax.shard_map`` over a
    4-chip "nodes" mesh, with shard_map's varying-axes check on."""
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels.radix_sort.radix_sort import bucket_argsort_pallas

    mesh = Mesh(np.array(topo.devices[:4]), ("nodes",),
                axis_types=(AxisType.Auto,))
    sort = jax.shard_map(
        functools.partial(bucket_argsort_pallas, num_buckets=8_001),
        mesh=mesh,
        in_specs=P("nodes"),
        out_specs=P("nodes"),
    )
    x = jax.ShapeDtypeStruct(
        (4 * EVENTS,), jnp.int32, sharding=NamedSharding(mesh, P("nodes"))
    )
    assert _holds_kernel(jax.jit(sort).lower(x))


@pytest.mark.parametrize("cap", [2 * EVENTS, 16_384])
def test_keyed_running_sum_compiles_one_bucket(one_chip, monkeypatch, cap):
    """One padding bucket of the jit tier's keyed running sum, float64, with
    the TPU's radix argsort — the shape Jobs 2 and 3 compile per bucket,
    against a table sized for a whole batch (2 × EVENTS) and one sized to
    twice its 5,000 keys (16,384)."""
    from repro.engine import jitexec as jx
    from repro.kernels.radix_sort import ops as radix_ops

    # The sort dispatches on the backend, which is the CPU here: steer it
    # to the TPU branch for this compile only.
    monkeypatch.setattr(radix_ops.jax, "default_backend", lambda: "tpu")
    s = functools.partial(_spec, one_chip)
    table = jx.TableState(
        s((cap,), jnp.int64),
        s((cap,), jnp.float64),
        s((cap,), jnp.int64),
        s((cap,), jnp.int32),
        s((cap,), jnp.int32),
        s((), jnp.int32),
        s((), jnp.int64),
    )
    lowered = jax.jit(jx.keyed_running_sum).lower(
        table,
        s((EVENTS,), jnp.int64),
        s((EVENTS,), jnp.int64),
        s((EVENTS,), jnp.float64),
        s((EVENTS,), jnp.bool_),
    )
    assert _holds_kernel(lowered)


def test_superstep_scan_compiles(one_chip, monkeypatch):
    """The fused record pipeline's K-tick ``lax.scan`` at the smoke's width
    (20 nodes × 400 key groups, 65,536 events per tick): the chip's
    compiler refuses a scan body whose scoped fast memory overflows."""
    from benchmarks.engine_throughput import _REC_SCHEMA, make_record_pipeline_job
    from repro.engine import Engine, ExecutionConfig
    from repro.engine import superstep as ss

    calls = []
    build = ss.SuperstepRuntime._build_scan

    def capture(self, *a, **k):
        scan = build(self, *a, **k)

        def call(*args):
            calls.append((scan, args))
            return scan(*args)

        return call

    monkeypatch.setattr(ss.SuperstepRuntime, "_build_scan", capture)
    engine = Engine(
        make_record_pipeline_job(num_keygroups=400, depth=3),
        20,
        config=ExecutionConfig.superstep().replace(kernel_stats=False),
        service_rate=1e9,
        seed=0,
    )
    rng = np.random.default_rng(0)
    batches = []
    for t in range(2):
        values = np.zeros(EVENTS, dtype=_REC_SCHEMA.value)
        values["a"] = rng.integers(0, 1_000, size=EVENTS)
        keys = rng.integers(0, 1_000_000, size=EVENTS)
        batches.append((keys, values, np.full(EVENTS, float(t))))
    assert engine.run_supersteps(batches) == 2
    (scan, args), = calls
    specs = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype), args
    )
    # A fresh function object: jit's trace cache is keyed by the function,
    # and this trace must be for the described chip.
    lowered = jax.jit(lambda *a: scan.__wrapped__(*a)).lower(*specs)
    lowered.compile()
