"""Sharding rules, constraint helper, and HLO collective census."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_census
from repro.configs.base import get_config
from repro.distributed import sharding as sh
from repro.distributed.ctx import constrain
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.training.loop import abstract_train_state


def _abstract_mesh(shape, names):
    """An abstract mesh with fake sizes (no devices needed for spec tests)."""
    return jax.sharding.AbstractMesh(shape, names)


MESH = _abstract_mesh((16, 16), ("data", "model"))
MESH3 = _abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def _specs(arch, mesh=MESH):
    cfg = get_config(arch)
    state = abstract_train_state(cfg)
    return cfg, state, sh.param_spec_tree(state["params"], mesh)


def test_param_rules_dense():
    cfg, state, specs = _specs("qwen2-1.5b")
    assert specs["embed"] == P("model", None)
    blk = specs["blocks"]
    assert blk["attn"]["wq"] == P(None, None, "model")     # stacked layers
    assert blk["attn"]["wo"] == P(None, "model", None)
    assert blk["mlp"]["w1"] == P(None, None, "model")
    assert blk["mlp"]["w2"] == P(None, "model", None)
    assert blk["ln1"]["scale"] == P(None, None)


def test_param_rules_moe_experts_sharded():
    cfg, state, specs = _specs("qwen3-moe-30b-a3b")
    blk = specs["blocks"]
    assert blk["moe"]["we1"] == P(None, "model", None, None)  # EP over model
    assert blk["moe"]["router"] == P(None, None, None)


def test_param_rules_ssm_families():
    _, _, specs = _specs("rwkv6-7b")
    blk = specs["blocks"]
    assert specs["lm_head"] == P(None, "model")
    assert blk["tm"]["w_r"] == P(None, None, "model")
    assert blk["tm"]["w_o"] == P(None, "model", None)
    _, _, zspecs = _specs("zamba2-7b")
    assert zspecs["blocks"]["mamba"]["in_proj"] == P(None, None, "model")
    # shared attention block is NOT stacked -> no leading None
    assert zspecs["shared"]["attn"]["wq"] == P(None, "model")


def test_batch_spec_divisibility():
    assert sh.batch_spec(MESH, 256) == P(("data",))
    assert sh.batch_spec(MESH3, 256) == P(("pod", "data"))
    assert sh.batch_spec(MESH3, 8) == P()              # 8 % 32 != 0
    assert sh.batch_spec(MESH, 1) == P()


def test_cache_spec_kv_layout():
    cfg = get_config("qwen2-1.5b")
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 128, 1024))
    specs = sh.cache_spec_tree(cache, MESH, 128)
    assert specs["k"] == P(None, ("data",), "model", None, None)
    # batch=1 (long-context): shard the sequence axis over everything
    specs1 = sh.cache_spec_tree(cache, MESH, 1)
    assert specs1["k"][1] is None
    assert specs1["k"][2] is not None


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = constrain(x, "B", "M")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_constrain_applies_inside_mesh():
    mesh = make_local_mesh()  # (n,1) on CPU

    @jax.jit
    def f(x):
        return constrain(x, "B", "M") * 2

    with mesh:
        out = f(jnp.ones((len(jax.devices()), 8)))
    assert np.asarray(out).sum() == len(jax.devices()) * 8 * 2


# ---------------------------------------------------------------------------
# HLO collective census
# ---------------------------------------------------------------------------


# Real XLA post-optimization HLO formatting: column-0 headers with tuple
# params (nested parens), layout suffixes, backend_config trip counts,
# iota replica_groups, and an async -start/-done pair.
SYNTH_HLO = """\
HloModule test, is_scheduled=true, num_partitions=256

%region_sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%body.7_spmd.clone (p.1: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %ag = f32[128,256]{1,0} all-gather(f32[8,256]{1,0} %x), replica_groups=[16,16]<=[256], dimensions={0}, metadata={op_name="x"}
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %ag), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%region_sum
  ROOT %t = (s32[], f32[128,256]{1,0}) tuple(%i, %ar)
}

%cond.8_spmd (p.2: (s32[], f32[128,256])) -> pred[] {
  %c = s32[] constant(24)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main.9_spmd (param.0: f32[128,256]) -> f32[128,256] {
  %w = (s32[], f32[128,256]{1,0}) while(%init), condition=%cond.8_spmd, body=%body.7_spmd.clone, backend_config={"known_trip_count":{"n":"24"}}
  %rs = f32[8,256]{1,0} reduce-scatter(f32[128,256]{1,0} %gte), replica_groups=[16,16]<=[256], dimensions={0}, to_apply=%region_sum
  %cps = (f32[4,4]{1,0}, f32[4,4]{1,0}) collective-permute-start(f32[4,4]{1,0} %y), source_target_pairs={{0,1}}
  %cpd = f32[4,4]{1,0} collective-permute-done(%cps)
  ROOT %out = f32[128,256]{1,0} get-tuple-element(%w), index=1
}
"""


def test_census_counts_ops_and_trip_counts():
    c = collective_census(SYNTH_HLO)
    assert c["n_ops"] == 4                       # -done is not an op
    assert c["while_trip_counts"] == {"body.7_spmd.clone": 24}
    ag_res = 128 * 256 * 4
    # all-gather operand = result/n, x24 loop trips
    np.testing.assert_allclose(c["per_op"]["all-gather"],
                               ag_res / 16 * 24)
    np.testing.assert_allclose(c["per_op"]["all-reduce"], ag_res * 24)
    np.testing.assert_allclose(c["per_op"]["reduce-scatter"],
                               8 * 256 * 4 * 16)
    np.testing.assert_allclose(c["per_op"]["collective-permute"], 4 * 4 * 4)
    assert c["total_bytes"] == sum(c["per_op"].values())
    # ring wire bytes: AR counts twice (n-1)/n
    assert c["wire_bytes"] > 0


def test_census_trip_count_fallback_from_condition():
    """Without backend_config, the trip count comes from the largest s32
    constant in the loop condition."""
    txt = SYNTH_HLO.replace(
        ', backend_config={"known_trip_count":{"n":"24"}}', "")
    c = collective_census(txt)
    assert c["while_trip_counts"] == {"body.7_spmd.clone": 24}


def test_census_on_real_compiled_module():
    """End-to-end: a compiled (1-device CPU) module parses without error;
    the dry-run JSONs provide the multi-device assertions."""
    txt = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile().as_text()
    c = collective_census(txt)
    assert c["n_ops"] == 0 and c["total_bytes"] == 0.0


def test_census_empty_module():
    c = collective_census("ENTRY %main () -> f32[] {\n ROOT %z = f32[] constant(0)\n}")
    assert c["n_ops"] == 0 and c["total_bytes"] == 0
