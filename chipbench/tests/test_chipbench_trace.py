"""The trace reduction, on a trace recorded on a TPU v5e: a `job3.steady`
window under the benchmark's own spans (see the file name in RECORDED)."""

import gzip
import math
import os

import numpy as np
import pytest

from chipbench import registry, trace
from chipbench.harness import SPANS

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "job3_steady_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData

    with open(RECORDED, "rb") as f:
        return ProfileData.from_serialized_xspace(gzip.decompress(f.read()))


def _brute_force(pd):
    """Busy and idle time at 1 us resolution, straight from the events."""
    window = [
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events if ev.name == "window"
    ]
    lo, hi = window[0]
    busy = np.zeros(int(math.ceil((hi - lo) / 1e3)) + 1, dtype=bool)
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e > s:
                    busy[int((s - lo) // 1e3): int(math.ceil((e - lo) / 1e3))] = True
    return (hi - lo) / 1e9, busy.sum() / 1e6


def test_busy_and_idle_agree_with_a_brute_force_reading(pd):
    r = trace.reduce(pd, SPANS)
    window_s, busy_s = _brute_force(pd)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(window_s, abs=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(busy_s, abs=2e-6 * 5000)
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert {n for n, _ in r["idle_gaps"]} <= set(SPANS) | {"other"}


def test_kernels_are_found_with_their_shapes(pd):
    r = trace.reduce(pd, SPANS)
    kinds = {k for k, _, _ in r["kernels"]}
    assert kinds == {"keygroup_partition", "bucket_argsort"}
    hist = [s for k, s, _ in r["kernels"] if k == "bucket_argsort" and len(s) == 1]
    assert hist and all(len(s[0]) == 3 for s in hist)
    names = [n for n, _ in r["device_ops"]]
    assert any(n.endswith(":bucket_argsort_pallas") for n in names)


def test_per_layer_readers_on_the_recorded_trace(pd):
    class Run:
        trace = None
        device_kind = "TPU v5 lite"
        window_ticks = 4

    run = Run()
    run.trace = trace.reduce(pd, SPANS)
    share = registry.metric_reader("bucket_argsort_roofline")(run)
    assert 0 < share < 100
    idle = registry.metric_reader("device_idle_share")(run)
    assert 0 < idle < 100
    assert registry.metric_reader("kernel_ms_per_tick")(run) > 0


def test_operand_shapes_of_hlo_text():
    text = (
        "%bucket_argsort_pallas.4 = s32[128,1,8192]{2,1,0:T(1,128)S(1)} "
        "custom-call(s32[128,1,512]{2,1,0:T(1,128)} %bitcast.10), "
        'custom_call_target="tpu_custom_call"'
    )
    assert trace.instruction(text) == "bucket_argsort_pallas"
    assert trace.kernel_of(text) == "bucket_argsort"
    assert trace.operand_shapes(text) == [(128, 1, 512)]
    two = "%x.1 = s32[4]{0} custom-call(s32[4]{0} %a, s32[2,3]{1,0} %b), y=z"
    assert trace.operand_shapes(two) == [(4,), (2, 3)]
    assert trace.kernel_of("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p)") is None
