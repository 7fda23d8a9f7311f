"""Sharding rules: logical axes → PartitionSpecs (AbstractMesh — no
devices needed)."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.dist import sharding as shd


def _mesh(multi=False):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def test_param_rules_fsdp_plus_tp():
    mesh = _mesh()
    spec = shd.spec_for(mesh, (1536, 8960), ("embed", "ffn"),
                        shd.PARAM_RULES)
    assert spec == P("data", "model")


def test_param_rules_multi_pod_fsdp_spans_pod_and_data():
    mesh = _mesh(multi=True)
    spec = shd.spec_for(mesh, (6144, 24576), ("embed", "ffn"),
                        shd.PARAM_RULES)
    assert spec == P(("pod", "data"), "model")


def test_non_divisible_dim_left_unsharded():
    mesh = _mesh()
    # 12 heads on a 16-way model axis: dropped, not padded
    spec = shd.spec_for(mesh, (28, 12, 128), ("layers", "heads", None),
                        shd.PARAM_RULES)
    assert spec == P()


def test_layers_scan_dim_never_sharded():
    mesh = _mesh()
    spec = shd.spec_for(mesh, (64, 5120, 5120), ("layers", "embed", "qkv"),
                        shd.PARAM_RULES)
    assert spec == P(None, "data", "model")


def test_no_axis_reuse_within_one_param():
    mesh = _mesh()
    # both dims map to "model" — second one must be dropped
    spec = shd.spec_for(mesh, (25600, 25600), ("ffn", "vocab"),
                        shd.PARAM_RULES)
    assert spec == P("model")


def test_every_arch_param_tree_builds_shardings():
    from repro.configs import all_arch_ids, get_config
    from repro.models.model import build_model
    mesh = _mesh()
    for arch in all_arch_ids():
        model = build_model(get_config(arch))           # FULL config
        sh = shd.param_shardings(mesh, model.abstract(), model.axes())
        leaves = jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: hasattr(x, "spec"))
        assert leaves, arch
        # every 2D+ float param ≥ 1M elements must be sharded somehow
        abs_leaves = jax.tree_util.tree_leaves(model.abstract())
        for a, s in zip(abs_leaves, leaves):
            import numpy as np
            if np.prod(a.shape) >= (1 << 22):
                assert len(s.spec) > 0, (arch, a.shape, s)


def test_batch_sharding_drops_non_divisible():
    mesh = _mesh()
    assert shd.batch_sharding(mesh, (256, 4096)).spec[0] == "data"
    assert shd.batch_sharding(mesh, (1,)).spec == P(None)
