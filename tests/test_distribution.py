"""Sharding rules, roofline parsing, and a reduced-mesh dry-run subprocess."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.launch.roofline import (
    CollectiveStats,
    analyze_hlo,
    chip_peaks,
    model_flops_estimate,
    parse_collectives,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# rules resolution (no devices needed — use a fake mesh view)
# ---------------------------------------------------------------------------


class FakeMesh:
    def __init__(self, **axes):
        self.shape = axes
        self.axis_names = tuple(axes)


def test_rules_divisibility():
    from repro.launch.sharding import rules_for

    mesh = FakeMesh(data=16, model=16)
    cfg = get_config("llama3_2_3b")  # 24 heads — not divisible by 16
    rules = rules_for(cfg, SHAPES["train_4k"], mesh)
    assert rules["heads"] is None
    assert rules["ff"] == "model"  # 8192 % 16 == 0
    assert rules["batch"] == ("data",)

    cfg2 = get_config("glm4_9b")  # 32 heads — divisible
    rules2 = rules_for(cfg2, SHAPES["train_4k"], mesh)
    assert rules2["heads"] == "model"


def test_rules_decode_cache():
    from repro.launch.sharding import rules_for

    mesh = FakeMesh(data=16, model=16)
    glm = get_config("glm4_9b")  # kv=2 → sequence-sharded cache
    r = rules_for(glm, SHAPES["decode_32k"], mesh)
    assert r["cache_heads"] is None and r["cache_seq"] == "model"
    gem = get_config("gemma_7b")  # kv=16 → head-sharded cache
    r2 = rules_for(gem, SHAPES["decode_32k"], mesh)
    assert r2["cache_heads"] == "model"


def test_rules_degenerate_batch():
    from repro.launch.sharding import rules_for

    mesh = FakeMesh(data=16, model=16)
    cfg = get_config("recurrentgemma_2b")
    rules = rules_for(cfg, SHAPES["long_500k"], mesh)  # batch 1
    assert rules["batch"] is None


# ---------------------------------------------------------------------------
# roofline HLO parsing
# ---------------------------------------------------------------------------

SAMPLE_HLO = textwrap.dedent(
    """
    HloModule jit_step

    %body.1 (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
      %p = (s32[], f32[8,8]) parameter(0)
      %lhs = f32[8,16]{1,0} parameter(1)
      %rhs = f32[16,8]{1,0} parameter(2)
      %dot.1 = f32[8,8]{1,0} dot(%lhs, %rhs), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %all-reduce.1 = f32[8,8]{1,0} all-reduce(%dot.1), replica_groups={{0,1,2,3}}, to_apply=%add
    }

    %cond.1 (arg: (s32[], f32[8,8])) -> pred[] {
      %c = s32[] constant(12)
      ROOT %cmp = pred[] compare(%c, %c), direction=LT
    }

    ENTRY %main (a: f32[8,16], b: f32[16,8]) -> f32[8,8] {
      %a = f32[8,16]{1,0} parameter(0)
      %b = f32[16,8]{1,0} parameter(1)
      %ag = f32[32,16]{1,0} all-gather(%a), replica_groups={{0,1,2,3}}, dimensions={0}
      %w = (s32[], f32[8,8]) while(%init), condition=%cond.1, body=%body.1
    }
    """
)


def test_parse_collectives_trip_weighting():
    stats = parse_collectives(SAMPLE_HLO)
    assert stats.counts["all-reduce"] == 1
    assert stats.counts["all-gather"] == 1
    # all-reduce inside the while body: 8·8·4 B × 2·(3/4) ring × 12 trips.
    ar = stats.wire_bytes["all-reduce"]
    assert abs(ar - (8 * 8 * 4) * 2 * 0.75 * 12) < 1e-6
    # all-gather in entry: result 32·16·4 × 3/4, once.
    ag = stats.wire_bytes["all-gather"]
    assert abs(ag - (32 * 16 * 4) * 0.75) < 1e-6


def test_analyze_hlo_flops_trip_weighting():
    a = analyze_hlo(SAMPLE_HLO)
    # dot inside the while body: 2·8·8·16 × 12 trips.
    assert abs(a.flops - 2 * 8 * 8 * 16 * 12) < 1e-6
    assert a.num_dots == 1
    assert a.hbm_bytes > 0


def test_chip_peaks_are_keyed_by_device_kind():
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")


def test_model_flops_estimates():
    cfg = get_config("glm4_9b")
    train = model_flops_estimate(cfg, SHAPES["train_4k"])
    assert abs(train - 6 * cfg.param_count() * 4096 * 256) / train < 1e-6
    moe = get_config("dbrx_132b")
    t2 = model_flops_estimate(moe, SHAPES["train_4k"])
    assert t2 < 6 * moe.param_count() * 4096 * 256  # active < total


# ---------------------------------------------------------------------------
# reduced-mesh dry run (subprocess: needs its own XLA_FLAGS)
# ---------------------------------------------------------------------------

SMALL_DRYRUN = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, dataclasses
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs import SHAPES, get_config
    from repro.launch import sharding as shd
    from repro.launch.roofline import analyze_hlo
    from repro.models import make_train_step
    from repro.models.common import activation_rules
    from repro.optim import AdamW

    mesh = jax.make_mesh(
        (2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
    cfg = get_config("dbrx_132b", smoke=True)
    cfg = dataclasses.replace(cfg, vocab_size=512, cycles=2)
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=4)
    rules = shd.rules_for(cfg, shape, mesh)
    opt = AdamW()
    with mesh, activation_rules(rules, mesh=mesh):
        p_shapes = shd.param_shapes(cfg)
        p_shard = shd.param_shardings(cfg, mesh, rules)
        o_shapes = shd.opt_shapes(cfg, opt)
        o_shard = shd.opt_shardings(cfg, mesh, rules)
        batch = {
            "tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
            "labels": jax.ShapeDtypeStruct((4, 64), jnp.int32),
        }
        b_shard = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        rep = NamedSharding(mesh, P())
        step = make_train_step(cfg, opt)
        lowered = jax.jit(
            step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, {"loss": rep, "grad_norm": rep}),
        ).lower(p_shapes, o_shapes, batch)
        compiled = lowered.compile()
        a = analyze_hlo(compiled.as_text())
        assert a.flops > 0, "no dot flops found"
        mem = compiled.memory_analysis()
        print("OK", a.flops, int(a.hbm_bytes), len(a.collectives.counts))
    """
)


@pytest.mark.slow
def test_small_mesh_dryrun_compiles():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SMALL_DRYRUN],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.startswith("OK")
