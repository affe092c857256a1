"""Logical-axis -> PartitionSpec rules (AbstractMesh: no devices needed)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import base
from repro.distributed import sharding
from repro.models import params as P_lib, transformer
from repro.serving import kvcache

MESH = sharding.abstract_mesh((16, 16), ("data", "model"))
POD_MESH = sharding.abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_divisible_dims_shard():
    spec = sharding.spec_for((2048, 8192), ("embed", "mlp"),
                             sharding.DEFAULT_RULES, MESH)
    assert spec == P("data", "model")


def test_indivisible_dims_replicate():
    # 9 heads don't divide 16 -> replicate that dim
    spec = sharding.spec_for((576, 9, 64), ("embed", "heads", "head_dim"),
                             sharding.DEFAULT_RULES, MESH)
    assert spec == P("data", None, None)


def test_mesh_axis_used_once_per_spec():
    rules = dict(sharding.DEFAULT_RULES, heads="model", mlp="model")
    spec = sharding.spec_for((32, 9728), ("heads", "mlp"), rules, MESH)
    flat = [a for part in spec if part for a in
            (part if isinstance(part, tuple) else (part,))]
    assert len(flat) == len(set(flat))


def test_tuple_rule_partial_divisibility():
    # vocab -> ('data','model'): 49152 divides 16 and 16*16
    spec = sharding.spec_for((49152,), ("vocab",), sharding.PURE_DP_RULES,
                             MESH)
    assert spec == P(("data", "model"))


def test_pod_never_shards_params():
    cfg = base.get("granite-3-2b")
    pspec = transformer.param_spec(cfg)
    specs = sharding.tree_partition_specs(
        P_lib.abstract(pspec), P_lib.logical_axes(pspec),
        sharding.DEFAULT_RULES, POD_MESH)
    for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        for part in s:
            parts = part if isinstance(part, tuple) else (part,)
            assert "pod" not in parts


@pytest.mark.parametrize("arch", base.ARCH_IDS)
def test_every_arch_has_valid_specs(arch):
    cfg = base.get(arch)
    pspec = transformer.param_spec(cfg)
    specs = sharding.tree_partition_specs(
        P_lib.abstract(pspec), P_lib.logical_axes(pspec),
        sharding.DEFAULT_RULES, MESH)
    abstract = P_lib.abstract(pspec)
    sizes = dict(MESH.shape)
    for leaf, s in zip(jax.tree.leaves(abstract),
                       jax.tree.leaves(specs,
                                       is_leaf=lambda x: isinstance(x, P))):
        for dim, part in zip(leaf.shape, tuple(s) + (None,) * 8):
            if part is None:
                continue
            parts = part if isinstance(part, tuple) else (part,)
            prod = 1
            for a in parts:
                prod *= sizes[a]
            assert dim % prod == 0, f"{arch}: {leaf.shape} vs {s}"


def test_batch_spec_divisibility():
    assert sharding.batch_spec(POD_MESH, 256) == P(("pod", "data"))
    assert sharding.batch_spec(POD_MESH, 2) == P("pod")
    assert sharding.batch_spec(POD_MESH, 1) == P()
    assert sharding.batch_spec(MESH, 32) == P("data")


def test_cache_seq_fallback_for_indivisible_kv_heads():
    cfg = base.get("qwen1.5-110b")  # kv=8 < model=16
    specs = kvcache.cache_partition_spec(cfg, 128, 32768, MESH)
    k_spec = specs["kv"]["k"]
    # (layers, batch, seq, kv_heads, head_dim): seq must take 'model'
    assert k_spec[2] == "model"


def test_cache_kv_heads_shard_when_divisible():
    # a GQA cache with kv=16 == model
    cfg = dataclasses.replace(base.get("granite-3-2b"), n_kv_heads=16)
    specs = kvcache.cache_partition_spec(cfg, 128, 32768, MESH)
    assert specs["kv"]["k"][3] == "model"
