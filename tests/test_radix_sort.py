"""Radix-sort kernel suite: the Pallas bucketed counting argsort must be
bit-identical (not allclose — identical permutations) to numpy's stable
radix argsort, the CPU data plane's routing sort, in interpret mode."""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    def _noop_decorator(*args, **kwargs):
        def wrap(fn):
            return fn

        return wrap

    given = settings = _noop_decorator

    class st:
        @staticmethod
        def integers(*args, **kwargs):
            return None

requires_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed"
)

from repro.kernels.radix_sort import bucket_argsort, bucket_argsort_jax
from repro.kernels.radix_sort.radix_sort import bucket_argsort_pallas
from repro.kernels.radix_sort.ref import bucket_argsort_ref


@pytest.mark.parametrize(
    "n,nb,block",
    [
        (0, 4, 512),       # empty
        (1, 1, 512),       # single element, single bucket
        (7, 3, 4),         # multiple partial blocks
        (512, 16, 512),    # exactly one block
        (513, 16, 512),    # one-past-block tail
        (1024, 2, 128),    # heavy duplicate pressure across blocks
        (2000, 257, 512),  # bucket count not a power of two
    ],
)
def test_pallas_matches_numpy_stable_argsort(n, nb, block):
    rng = np.random.default_rng(n * 31 + nb)
    codes = rng.integers(0, nb, size=n).astype(np.int32)
    ref = bucket_argsort_ref(codes)
    if n == 0:
        assert bucket_argsort(codes, nb).size == 0
        return
    out = bucket_argsort_pallas(
        jnp.asarray(codes), num_buckets=nb, block=block, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_all_equal_codes_keep_input_order():
    codes = np.zeros(300, dtype=np.int32)
    out = bucket_argsort_pallas(
        jnp.asarray(codes), num_buckets=1, block=64, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(out), np.arange(300))


def test_host_dispatch_cpu_uses_numpy_handoff():
    """On CPU the host wrapper is numpy's radix argsort verbatim."""
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 40, size=777)
    np.testing.assert_array_equal(
        bucket_argsort(codes, 40), np.argsort(codes, kind="stable")
    )


def test_host_dispatch_force_pallas_interpret():
    rng = np.random.default_rng(1)
    codes = rng.integers(0, 12, size=333)
    np.testing.assert_array_equal(
        bucket_argsort(codes, 12, force_pallas=True),
        np.argsort(codes, kind="stable"),
    )


def test_traceable_entry_matches_numpy():
    """bucket_argsort_jax (the fused superstep's in-jit routing sort) must
    produce the identical stable permutation on every backend."""
    rng = np.random.default_rng(2)
    codes = rng.integers(0, 64, size=1500).astype(np.int64)
    out = bucket_argsort_jax(jnp.asarray(codes), 64)
    np.testing.assert_array_equal(
        np.asarray(out), np.argsort(codes, kind="stable")
    )


@requires_hypothesis
@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 1500),
    nb=st.integers(1, 300),
    block=st.integers(1, 9),
    seed=st.integers(0, 10_000),
)
def test_property_bit_identical_permutation(n, nb, block, seed):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, nb, size=n).astype(np.int32)
    out = bucket_argsort_pallas(
        jnp.asarray(codes),
        num_buckets=nb,
        block=2**block,
        interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(out), np.argsort(codes, kind="stable")
    )


def test_radix_argsort_tpu_passes_compose_to_a_stable_sort(monkeypatch):
    """The TPU branch of radix_argsort_jax (8-bit LSD passes of the kernel)
    run here with the kernel interpreted: int64 codes, the running sums'
    padding code included, sort exactly as numpy's stable argsort."""
    import functools

    import jax

    from repro.kernels.radix_sort import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        ops,
        "bucket_argsort_pallas",
        functools.partial(bucket_argsort_pallas, interpret=True),
    )
    rng = np.random.default_rng(4)
    codes = rng.integers(0, 2**62, size=700)
    codes[::5] = codes[1::5][: len(codes[::5])]  # ties
    codes[::7] = np.iinfo(np.int64).max
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)  # as the jit tier runs it
    try:
        out = ops.radix_argsort_jax(jnp.asarray(codes, dtype=jnp.int64))
    finally:
        jax.config.update("jax_enable_x64", x64)
    np.testing.assert_array_equal(
        np.asarray(out), np.argsort(codes, kind="stable")
    )


@pytest.mark.parametrize("nb", [0, 2**31 - 1])
def test_dispatchers_refuse_bucket_counts_the_kernel_cannot_serve(nb):
    codes = np.zeros(10, dtype=np.int64)
    with pytest.raises(ValueError, match="serves"):
        bucket_argsort(codes, nb, force_pallas=True)
