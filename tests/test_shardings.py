"""Sharding-rule tests: divisibility guards, axis-conflict resolution,
ZeRO-1 moment sharding — on an AbstractMesh shaped like the production pod."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.planner import ShardingPlan
from repro.launch import shardings as S
from repro.launch.mesh import abstract_mesh
from repro.models.model import build_model

MESH = abstract_mesh((16, 16), ("data", "model"))
PLAN_TP = ShardingPlan(batch_axes=("data",), tp_axes=("model",))
PLAN_EPTP = ShardingPlan(batch_axes=("data",), tp_axes=("model",),
                         ep_axes=("model",))


def _flat_specs(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(S._pstr(p) for p in path): leaf.spec
            for path, leaf in flat}


def test_no_axis_used_twice_in_any_spec():
    for arch_id in ("deepseek-v3-671b", "phi3.5-moe-42b-a6.6b",
                    "gemma3-12b", "mamba2-1.3b", "whisper-small"):
        shapes = build_model(get_config(arch_id)).init_shapes()
        specs = _flat_specs(S.params_shardings(MESH, PLAN_EPTP, shapes))
        for key, spec in specs.items():
            used = []
            for entry in spec:
                if entry is None:
                    continue
                used += list(entry) if isinstance(entry, tuple) else [entry]
            assert len(used) == len(set(used)), (arch_id, key, spec)


def test_divisibility_guard_falls_back_to_replication():
    # whisper has 12 heads; 12 q-heads x 64 = 768 columns: 768 % 16 == 0 so
    # the flat dim shards; but a 10-wide dim must stay replicated
    sh = S.param_sharding(MESH, PLAN_TP, "blocks/attn/w_q", (12, 768, 770))
    assert sh.spec[1] in ("model", None)
    sh2 = S.param_sharding(MESH, PLAN_TP, "blocks/attn/w_q", (12, 768, 10))
    assert sh2.spec[-1] is None


def test_moe_experts_shard_over_ep():
    sh = S.param_sharding(MESH, PLAN_EPTP, "blocks/moe/w_up", (58, 256, 7168, 2048))
    assert sh.spec[1] == "model"         # experts win the model axis
    assert sh.spec[3] is None            # tp lost the tie -> replicated


def test_batch_sharding_divides_batch_dim():
    shapes = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    sh = S.batch_shardings(MESH, PLAN_TP, shapes)
    assert sh["tokens"].spec[0] == "data"
    odd = {"tokens": jax.ShapeDtypeStruct((7, 64), jnp.int32)}
    assert S.batch_shardings(MESH, PLAN_TP, odd)["tokens"].spec[0] is None


def test_cache_seq_fallback_when_batch_unshardable():
    # long_500k: batch=1 -> KV length dim takes the data axis
    shapes = {"self": {"kv": jax.ShapeDtypeStruct((48, 524288, 8, 1, 512),
                                                  jnp.bfloat16)}}
    sh = S.cache_shardings(MESH, PLAN_TP, shapes)
    assert sh["self"]["kv"].spec[3] is None
    assert sh["self"]["kv"].spec[1] == "data"


@pytest.mark.parametrize("arch_id", [
    "qwen1.5-0.5b", "deepseek-v3-671b", "phi3.5-moe-42b-a6.6b", "gemma3-12b",
    "mamba2-1.3b", "zamba2-2.7b", "whisper-small"])
def test_cache_specs_match_each_leaf_rank(arch_id):
    """No decode cache leaf gets more spec entries than it has dimensions;
    the MLA latents ([L, B, S, r], named ``ckv``) keep batch over data and
    the slot-major ``kv`` [L, S, H, B, 2D] shards its batch dim."""
    shapes = build_model(get_config(arch_id)).cache_shapes(32, 4096)
    sh = S.cache_shardings(MESH, PLAN_TP, shapes)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    specs = _flat_specs(sh)
    for path, leaf in leaves:
        key = "/".join(S._pstr(p) for p in path)
        assert len(specs[key]) <= leaf.ndim, (key, leaf.shape, specs[key])
        if key.endswith("ckv"):
            assert specs[key][1] == "data", (key, specs[key])
        if key.endswith("/kv"):
            assert specs[key][3] == "data", (key, specs[key])


def test_zero1_moments_pick_up_data_axis():
    from repro.optim import adamw
    shapes = build_model(get_config("qwen1.5-0.5b")).init_shapes()
    psh = S.params_shardings(MESH, PLAN_TP, shapes)
    opt_shapes = jax.eval_shape(
        lambda: adamw.init(adamw.AdamWConfig(), shapes))
    osh = S.opt_state_shardings(MESH, PLAN_TP, psh, opt_shapes)
    m_specs = _flat_specs(osh.m)
    p_specs = _flat_specs(psh)
    extra = sum("data" in str(m) and "data" not in str(p_specs[k])
                for k, m in m_specs.items())
    assert extra > 0, "ZeRO-1 should shard some moments over data"
