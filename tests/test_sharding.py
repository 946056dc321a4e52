"""Sharding rules, roofline HLO parsing, and an 8-device subprocess dry-run
(tests themselves keep the real 1-device view)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.launch import roofline as rl

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# ShardingPlan resolution rules (pure logic, no devices needed)
# ---------------------------------------------------------------------------


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def _plan(cfg, shape=None, **kw):
    from repro.distributed.sharding import make_sharding_plan

    mesh = FakeMesh({"data": 16, "model": 16})
    return make_sharding_plan(cfg, mesh, shape, **kw)


def test_heads_shard_when_divisible():
    plan = _plan(get_config("gemma2-27b"))           # 32 heads / 16
    assert plan.rules["heads"] == "model"
    assert plan.rules["act_seq"] is None


def test_sequence_sharding_fallback_for_odd_heads():
    plan = _plan(get_config("llama4-maverick-400b-a17b"))   # 40 heads
    assert plan.rules["heads"] is None
    assert plan.rules["act_seq"] == "model"
    plan2 = _plan(get_config("minitron-4b"))                # 24 heads
    assert plan2.rules["act_seq"] == "model"


def test_long_context_decode_shards_cache_sequence():
    cfg = get_config("jamba-1.5-large-398b")
    plan = _plan(cfg, SHAPES["long_500k"])
    assert plan.rules["cache_seq"] == ("data",)
    assert plan.rules["act_batch"] is None           # B=1 can't shard


def test_spec_for_drops_indivisible_dims():
    plan = _plan(get_config("gemma2-27b"))
    spec = plan.spec_for(("act_batch", "act_seq", "act_heads", None),
                         (6, 128, 32, 128))          # batch 6 !% 16
    assert spec[0] is None
    spec2 = plan.spec_for(("embed", "mlp"), (4608, 36864))
    assert spec2 == __import__("jax").sharding.PartitionSpec(
        ("data",), "model")


def test_one_mesh_axis_shards_at_most_one_dim():
    plan = _plan(get_config("xlstm-125m"))
    # mlstm wq: ("inner", "inner") — second occurrence must drop
    spec = plan.spec_for(("inner", "inner"), (1536, 1536))
    assert spec[0] == "model" and (len(spec) < 2 or spec[1] is None)


# ---------------------------------------------------------------------------
# roofline HLO parsing
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
  %all-reduce.4 = (f32[1024,4096]{1,0}, f32[4096,1024]{1,0}) all-reduce(%a, %b), replica_groups=[16,32]<=[32,16]T(1,0), use_global_device_ids=true
  %ag = bf16[256,512]{1,0} all-gather(%c), replica_groups=[8,64]<=[512], dimensions={0}
  %rs = f32[128]{0} reduce-scatter(%d), replica_groups=[4,128]<=[512]
  %cp = collective-permute-start(%e), source_target_pairs={{0,1}}
  %a2a = f32[64,64]{1,0} all-to-all(%f), replica_groups=[16,32]<=[512]
"""


def test_parse_collectives_kinds_and_groups():
    out = rl.parse_collectives(HLO_SAMPLE, 512)
    assert out["all-reduce"].count == 1
    ar_bytes = (1024 * 4096 + 4096 * 1024) * 4
    assert out["all-reduce"].result_bytes == ar_bytes
    np.testing.assert_allclose(out["all-reduce"].wire_bytes,
                               2 * ar_bytes * 15 / 16)
    ag_bytes = 256 * 512 * 2
    np.testing.assert_allclose(out["all-gather"].wire_bytes,
                               ag_bytes * 7 / 8)
    rs_bytes = 128 * 4
    np.testing.assert_allclose(out["reduce-scatter"].wire_bytes,
                               rs_bytes * 3)
    assert out["all-to-all"].count == 1


def test_extrapolation_linear():
    c2 = (10.0, 100.0, {"all-reduce": rl.CollectiveStats(2, 20, 40.0)})
    c4 = (14.0, 140.0, {"all-reduce": rl.CollectiveStats(4, 40, 80.0)})
    f, b, colls = rl.extrapolate_costs(c2, c4, 2, 4, 10)
    assert f == pytest.approx(10 + (4 / 2) * 8)      # base + slope*(10-2)
    assert b == pytest.approx(100 + 20 * 8)
    assert colls["all-reduce"].wire_bytes == pytest.approx(40 + 20 * 8)


def test_peaks_keyed_by_device_kind():
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND

    v5e = rl.peaks_for(PRODUCTION_DEVICE_KIND)
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9) and v5e.source
    with pytest.raises(ValueError, match="no peaks"):
        rl.peaks_for("cpu")


def test_model_flops_formulas():
    cfg = get_config("codeqwen1.5-7b")
    t = rl.model_flops(cfg, SHAPES["train_4k"])
    p = rl.model_flops(cfg, SHAPES["prefill_32k"])
    d = rl.model_flops(cfg, SHAPES["decode_32k"])
    n = cfg.active_param_count()
    assert t == pytest.approx(6 * n * 256 * 4096)
    assert p == pytest.approx(2 * n * 32 * 32768)
    assert d == pytest.approx(2 * n * 128)


# ---------------------------------------------------------------------------
# 8-device subprocess dry-run (reduced config, both meshes)
# ---------------------------------------------------------------------------

SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import smoke_config, SHAPES
from repro.distributed.sharding import make_sharding_plan
from repro.models import build_model
from repro.train import train_step as ts
from repro.launch import roofline as rl

results = {}
for mesh_shape, axes in (((4, 2), ("data", "model")),
                         ((2, 2, 2), ("pod", "data", "model"))):
    mesh = jax.make_mesh(mesh_shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
    cfg = smoke_config("gemma2-27b")
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64,
                                global_batch=8)
    plan = make_sharding_plan(cfg, mesh, shape)
    model = build_model(cfg)
    step = ts.make_train_step(model, cfg, plan=plan)
    state_sh = plan.tree_shardings(ts.state_axes(model),
                                   ts.state_shapes(model))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    batch_sh = plan.tree_shardings(model.input_axes(SHAPES["train_4k"]),
                                   batch)
    with mesh:
        lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                          donate_argnums=(0,)).lower(
            ts.state_shapes(model), batch)
        compiled = lowered.compile()
    colls = rl.parse_collectives(compiled.as_text(), mesh.devices.size)
    results["x".join(map(str, mesh_shape))] = {
        "collectives": sorted(colls),
        "flops": rl.extract_costs(compiled, mesh.devices.size)[0],
    }
print(json.dumps(results))
"""


@pytest.mark.slow
def test_subprocess_dryrun_8_devices():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert "4x2" in res and "2x2x2" in res
    # sharded training must communicate
    assert "all-reduce" in res["4x2"]["collectives"] \
        or "reduce-scatter" in res["4x2"]["collectives"]
    assert res["2x2x2"]["flops"] > 0
