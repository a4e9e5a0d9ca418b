"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode,
plus the engine wiring of the keygroup_partition histogram into SPL stats."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests skip cleanly without hypothesis; the rest still run
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    def _noop_decorator(*args, **kwargs):
        def wrap(fn):
            return fn

        return wrap

    given = settings = _noop_decorator

    class st:  # minimal strategy stand-ins so decorator args still evaluate
        @staticmethod
        def sampled_from(values):
            return None

        @staticmethod
        def integers(*args, **kwargs):
            return None


requires_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed"
)

from repro.kernels.decode_attention.decode_attention import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.moe_gemm.moe_gemm import moe_gemm_pallas
from repro.kernels.moe_gemm.ref import moe_gemm_ref
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.rglru_scan.rglru_scan import rglru_scan_pallas

TOL = {
    jnp.float32: dict(atol=3e-5, rtol=3e-5),
    jnp.bfloat16: dict(atol=3e-2, rtol=3e-2),
}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,s,h,kv,hd,causal,window,dtype",
    [
        (1, 256, 4, 2, 64, True, None, jnp.float32),
        (2, 256, 4, 4, 32, True, None, jnp.float32),
        (1, 512, 8, 2, 64, True, 128, jnp.float32),
        (1, 256, 4, 1, 64, False, None, jnp.float32),
        (1, 256, 8, 8, 128, True, None, jnp.bfloat16),
        (2, 384, 6, 3, 64, True, None, jnp.float32),  # uneven block tail-free
    ],
)
def test_flash_attention_matches_ref(b, s, h, kv, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), dtype)
    bq = 128 if s % 128 == 0 else 64
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=bq, block_kv=bq, interpret=True
    )
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


@requires_hypothesis
@settings(max_examples=6, deadline=None)
@given(
    s=st.sampled_from([128, 256]),
    h=st.sampled_from([2, 4]),
    g=st.sampled_from([1, 2]),
    seed=st.integers(0, 100),
)
def test_property_flash_attention(s, h, g, seed):
    kv = max(h // g, 1)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, s, h, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, s, kv, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, s, kv, 32), jnp.float32)
    out = flash_attention_pallas(q, k, v, block_q=64, block_kv=64, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,h,kv,hd,t,dtype",
    [
        (3, 8, 2, 64, 512, jnp.float32),
        (1, 4, 4, 32, 256, jnp.float32),
        (2, 16, 2, 128, 512, jnp.bfloat16),
        (1, 2, 1, 64, 1024, jnp.float32),
    ],
)
def test_decode_attention_matches_ref(b, h, kv, hd, t, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd), dtype)
    kc = jax.random.normal(ks[1], (b, t, kv, hd), dtype)
    vc = jax.random.normal(ks[2], (b, t, kv, hd), dtype)
    kv_len = jnp.asarray(
        np.random.default_rng(0).integers(1, t + 1, size=b), jnp.int32
    )
    out = decode_attention_pallas(q, kc, vc, kv_len, block_kv=128, interpret=True)
    ref = decode_attention_ref(q, kc, vc, kv_len)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,s,w,bs,bw",
    [(2, 256, 256, 64, 128), (1, 128, 512, 128, 128), (3, 512, 128, 256, 128)],
)
def test_rglru_scan_matches_ref(b, s, w, bs, bw):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    a = jax.random.uniform(ks[0], (b, s, w), jnp.float32, 0.2, 0.999)
    bb = jax.random.normal(ks[1], (b, s, w), jnp.float32) * 0.1
    h0 = jax.random.normal(ks[2], (b, w), jnp.float32)
    out = rglru_scan_pallas(a, bb, h0, block_seq=bs, block_width=bw, interpret=True)
    ref = rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@requires_hypothesis
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1000))
def test_property_rglru_scan_stability(seed):
    """With |a|<1 the recurrence must stay bounded (no blow-up)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.uniform(ks[0], (1, 128, 128), jnp.float32, 0.0, 0.99)
    b = jax.random.normal(ks[1], (1, 128, 128), jnp.float32)
    h0 = jnp.zeros((1, 128))
    out = rglru_scan_pallas(a, b, h0, block_seq=64, block_width=128, interpret=True)
    bound = float(jnp.abs(b).max()) / (1.0 - 0.99) + 1.0
    assert float(jnp.abs(out).max()) <= bound


# ---------------------------------------------------------------------------
# moe gemm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "e,c,d,f,dtype",
    [
        (4, 128, 256, 128, jnp.float32),
        (8, 64, 128, 256, jnp.float32),
        (2, 256, 512, 128, jnp.bfloat16),
    ],
)
def test_moe_gemm_matches_ref(e, c, d, f, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (e, c, d), dtype)
    w = (jax.random.normal(ks[1], (e, d, f), jnp.float32) * 0.05).astype(dtype)
    out = moe_gemm_pallas(x, w, block_c=64, block_d=128, block_f=64, interpret=True)
    ref = moe_gemm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **TOL[dtype]
    )


# ---------------------------------------------------------------------------
# keygroup_partition histogram wiring into SPL statistics
# ---------------------------------------------------------------------------


def _mk_pipeline(kgs=32):
    from repro.engine.topology import OperatorSpec, Topology

    def fwd(state, keys, values, ts):
        state["n"] = state.get("n", 0) + len(keys)
        return state, (keys + 5, values, ts)

    def sink(state, keys, values, ts):
        state["n"] = state.get("n", 0) + len(keys)
        return state, []

    t = Topology()
    t.add_operator(OperatorSpec("src", None, num_keygroups=kgs, is_source=True))
    t.add_operator(OperatorSpec("mid", fwd, num_keygroups=kgs))
    t.add_operator(OperatorSpec("snk", sink, num_keygroups=kgs, is_sink=True))
    t.connect("src", "mid")
    t.connect("mid", "snk")
    return t


def test_kernel_histogram_wiring_matches_numpy_engine():
    """kernel_stats=True feeds the kernel's histogram into SPLWindow —
    routing, arrivals, and folded SPL statistics stay bit-identical to the
    numpy (np.bincount) engine."""
    from repro.engine import Engine, ExecutionConfig

    kern = Engine(_mk_pipeline(), 4, service_rate=1e9, seed=0,
                  config=ExecutionConfig(kernel_stats=True))
    ref = Engine(_mk_pipeline(), 4, service_rate=1e9, seed=0,
                 config=ExecutionConfig(kernel_stats=False))
    rng = np.random.default_rng(5)
    for t in range(4):
        keys = rng.integers(-(2**62), 2**62, size=257, dtype=np.int64)
        vals = rng.random(257)
        for eng in (kern, ref):
            eng.push_source("src", keys, vals, np.full(257, float(t)))
            eng.tick()
    for _ in range(3):
        kern.tick()
        ref.tick()
    assert np.array_equal(kern.window.kg_arrivals, ref.window.kg_arrivals)
    assert kern.window.kg_arrivals.sum() > 0
    assert kern.metrics.processed_tuples == ref.metrics.processed_tuples
    s1, s2 = kern.end_period(), ref.end_period()
    assert np.array_equal(s1.kg_load, s2.kg_load)
    assert np.array_equal(s1.kg_tuple_rate, s2.kg_tuple_rate)
    assert np.array_equal(s1.out_rates, s2.out_rates)


def test_kernel_stats_is_decided_from_the_backend_at_construction(monkeypatch):
    """kernel_stats=None: the device tiers take the partition kernel exactly
    when jax's backend is a TPU, whatever was imported before; the numpy
    tiers never ask; a failing backend query propagates."""
    import jax

    from repro.data.jobs import real_job_2
    from repro.engine import Engine, ExecutionConfig

    def build(config):
        return Engine(real_job_2(keygroups_per_op=4), 2, config=config)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert build(ExecutionConfig.jit()).kernel_stats
    assert build(ExecutionConfig.superstep()).kernel_stats
    assert not build(ExecutionConfig.typed()).kernel_stats
    assert not build(ExecutionConfig.jit().replace(kernel_stats=False)).kernel_stats
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not build(ExecutionConfig.jit()).kernel_stats

    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="backend failed"):
        build(ExecutionConfig.jit())


def test_kernel_histogram_wiring_nonint_keys_fall_back():
    """String keys can't ride the int-mix kernel: the engine silently uses
    the numpy path and the statistics remain correct."""
    from repro.engine import Engine, ExecutionConfig
    from repro.engine.topology import OperatorSpec, Topology

    def sink(state, keys, values, ts):
        return state, []

    t = Topology()
    t.add_operator(OperatorSpec("src", None, num_keygroups=8, is_source=True))
    t.add_operator(OperatorSpec("snk", sink, num_keygroups=8, is_sink=True))
    t.connect("src", "snk")
    eng = Engine(t, 2, service_rate=1e9, seed=0,
                 config=ExecutionConfig(kernel_stats=True))
    keys = np.array([f"user-{i % 13}" for i in range(99)])
    eng.push_source("src", keys, np.ones(99), np.zeros(99))
    eng.tick()
    eng.tick()
    assert eng.metrics.processed_tuples == 2 * 99
    assert eng.window.kg_arrivals.sum() == 2 * 99


def test_window_record_arrivals_accumulates_histogram():
    """SPLWindow.record_arrivals adds a kernel histogram at the op's base."""
    from repro.core.stats import SPLWindow

    w = SPLWindow(16)
    w.record_arrivals(4, np.array([1, 2, 3]))
    w.record_arrivals(4, np.array([1, 0, 1]))
    assert w.kg_arrivals[4:7].tolist() == [2.0, 2.0, 4.0]
    assert w.kg_arrivals.sum() == 8.0
    w.reset()
    assert w.kg_arrivals.sum() == 0.0
