"""Sharding rules + autoshard legality + dispatch decisions."""

import math

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ALL_ARCHS, SHAPES, get_config
from repro.distributed.autoshard import best_rules, candidate_rules, predict_cell
from repro.distributed.sharding import ShardingRules, constrain, use_rules

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_spec_for_basic():
    r = ShardingRules(None, {"batch": ("pod", "data"), "ffn": "model"})
    assert r.spec_for(("batch", "seq", "ffn")) == P(("pod", "data"), None, "model")
    assert r.spec_for((None, "unknown")) == P(None, None)


def test_spec_for_no_axis_reuse():
    """One mesh axis cannot shard two dims of the same tensor."""
    r = ShardingRules(None, {"a": "model", "b": "model"})
    assert r.spec_for(("a", "b")) == P("model", None)


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("mesh", [MESH, MESH3], ids=["single", "multi"])
def test_candidates_divisibility(arch, mesh):
    """Every candidate rule table only shards divisible dims (the MATCH
    'pattern constraint' at pod level)."""
    cfg = get_config(arch)
    axes = dict(mesh.shape)
    for shape_name, cell in SHAPES.items():
        cands = candidate_rules(cfg, mesh, global_batch=cell.global_batch, seq=cell.seq_len)
        for name, rules in cands.items():
            t = rules.table

            def shards(key):
                v = t.get(key)
                if v is None:
                    return 1
                vv = (v,) if isinstance(v, str) else v
                return math.prod(axes[a] for a in vv)

            assert cell.global_batch % shards("batch") == 0, (arch, shape_name, name)
            assert cfg.d_model % shards("embed") == 0, (arch, name)
            if cfg.n_heads:
                assert cfg.n_heads % shards("heads") == 0
            if cfg.is_moe:
                assert cfg.n_experts % shards("experts") == 0
                assert cfg.moe_d_ff % shards("moe_ffn") == 0
            assert cfg.vocab % shards("vocab") == 0


def test_granite_moe_cannot_use_ep():
    """40 experts % 16 != 0: the dispatcher must not offer EP (paper-style
    constraint rejection) and must fall back to TP-sharded expert hidden."""
    cfg = get_config("granite_moe_3b_a800m")
    cands = candidate_rules(cfg, MESH, global_batch=256, seq=4096)
    for name, rules in cands.items():
        assert rules.table.get("experts") != "model", name
    # the TP candidate must shard the per-expert hidden dim instead
    assert cands["tp"].table.get("moe_ffn") == "model"


def test_dbrx_offers_both_ep_and_tp_experts():
    cfg = get_config("dbrx_132b")
    cands = candidate_rules(cfg, MESH, global_batch=256, seq=4096)
    assert any(r.table.get("experts") == "model" for r in cands.values())
    assert any(r.table.get("moe_ffn") == "model" for r in cands.values())


def test_best_rules_feasible_for_all_cells():
    from repro.configs import cell_applicable

    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        for shape_name, cell in SHAPES.items():
            if not cell_applicable(cfg, shape_name)[0]:
                continue
            for mesh in (MESH, MESH3):
                name, rules, cost = best_rules(
                    cfg, mesh, global_batch=cell.global_batch, seq=cell.seq_len, kind=cell.kind
                )
                assert cost.feasible, (arch, shape_name, name, cost.reason)
                assert cost.hbm_bytes_per_chip < 16 * 2**30


def test_constrain_noop_without_rules():
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    assert constrain(x, "batch", None) is x


def test_constrain_applies_inside_mesh():
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(1, 1)
    rules = ShardingRules(mesh, {"batch": "data"})
    with use_rules(rules):
        y = jax.jit(lambda x: constrain(x * 2, "batch", None))(jnp.ones((4, 4)))
    np.testing.assert_array_equal(np.asarray(y), 2 * np.ones((4, 4)))


def test_big_models_pick_fsdp_variants():
    """132B/34B training cannot fit without FSDP; the argmin must pick a
    parameter-sharded strategy."""
    for arch in ("dbrx_132b", "granite_34b"):
        cfg = get_config(arch)
        name, rules, cost = best_rules(cfg, MESH, global_batch=256, seq=4096, kind="train")
        assert rules.table.get("embed") is not None, (arch, name)
