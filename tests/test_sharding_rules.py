"""Partition-rule unit tests on an abstract 16x16 production mesh
(no devices needed — pure spec logic)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.distributed.sharding import batch_spec, cache_spec, param_spec
from repro import perf

MESH = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
MESH3 = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_attention_tp_when_heads_divide():
    cfg = get_arch("granite-34b")  # 48 heads % 16 == 0
    s = param_spec("layers/attn/q/w", (88, 6144, 6144), MESH, cfg)
    assert s == P(None, "model", "data")
    s = param_spec("layers/attn/o/w", (88, 6144, 6144), MESH, cfg)
    assert s == P(None, "data", "model")


def test_attention_fsdp_fallback_when_heads_dont_divide():
    cfg = get_arch("qwen2-0.5b")  # 14 heads % 16 != 0
    s = param_spec("layers/attn/q/w", (24, 896, 896), MESH, cfg)
    assert s == P(None, None, "data")  # no model sharding


def test_kv_projection_follows_kv_heads():
    cfg = get_arch("qwen3-1.7b")  # q heads 16 ok, kv heads 8 not
    assert param_spec("layers/attn/q/w", (28, 2048, 2048), MESH, cfg) == P(
        None, "model", "data")
    assert param_spec("layers/attn/k/w", (28, 1024, 2048), MESH, cfg) == P(
        None, None, "data")


def test_mlp_and_head_rules():
    cfg = get_arch("internlm2-20b")
    assert param_spec("layers/mlp/gate/w", (48, 16384, 6144), MESH, cfg) == P(
        None, "model", "data")
    assert param_spec("layers/mlp/down/w", (48, 6144, 16384), MESH, cfg) == P(
        None, "data", "model")
    assert param_spec("lm_head/w", (92544, 6144), MESH, cfg) == P(
        "model", "data")
    # embeddings: gather-local, FSDP on feature dim only
    assert param_spec("embed", (92544, 6144), MESH, cfg) == P(None, "data")


def test_moe_expert_parallel():
    cfg = get_arch("olmoe-1b-7b")
    assert param_spec("layers/moe/gate", (16, 64, 1024, 2048), MESH, cfg) == P(
        None, "model", None, "data")
    assert param_spec("layers/moe/router/w", (16, 64, 2048), MESH, cfg) == P()


def test_norms_and_scalars_replicate():
    assert param_spec("layers/attn_norm/scale", (88, 6144), MESH) == P()
    assert param_spec("opt/step", (), MESH) == P()


def test_non_divisible_dims_fall_back():
    cfg = get_arch("mamba2-130m")
    # in_proj out dim 3352 % 16 != 0 -> no model sharding; in dim 768 % 16
    s = param_spec("layers/in_proj/w", (24, 3352, 768), MESH, cfg)
    assert s == P(None, None, "data")


def test_batch_specs():
    assert batch_spec((256, 4096), MESH) == P("data", None)
    assert batch_spec((256, 4096), MESH3) == P(("pod", "data"), None)
    assert batch_spec((1, 524288), MESH) == P(None, None)  # B=1 replicates


def test_cache_specs_head_vs_seq():
    # kv heads 16 -> head sharding
    assert cache_spec("kv/k", (16, 128, 32768, 16, 128), MESH) == P(
        None, "data", None, "model", None)
    # MQA kv=1 -> sequence sharding fallback
    assert cache_spec("kv/k", (88, 128, 32768, 1, 128), MESH) == P(
        None, "data", "model", None, None)
    # scalar position replicates
    assert cache_spec("pos", (), MESH) == P()


def test_fsdp_sp_variant_disables_tp():
    cfg = get_arch("granite-34b")
    with perf.variant(perf.PerfVariant(fsdp_sp=True)):
        s = param_spec("layers/attn/q/w", (88, 6144, 6144), MESH, cfg)
    assert s == P(None, "model", "data")  # 2-D storage sharding
    with perf.variant(perf.PerfVariant(fsdp_sp=True)):
        s = param_spec("layers/mlp/gate/w", (88, 16384, 6144), MESH, cfg)
    assert s == P(None, "model", "data")


def test_pod_axis_in_multi_mesh():
    # params never shard over pod; batch does (tested above)
    cfg = get_arch("granite-34b")
    s = param_spec("layers/attn/q/w", (88, 6144, 6144), MESH3, cfg)
    assert "pod" not in jax.tree.leaves(tuple(s)) if s else True
    assert s == P(None, "model", "data")
