"""Sharding-rule unit tests + small-mesh integration (pjit on forced
multi-device CPU is covered by the dry-run; here: rule resolution,
divisibility fallback, and collective equivalence under shard_map)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import get_config
from repro.parallel.compression import quantize_int8
from repro.parallel.sharding import (SERVE_RULES, TRAIN_RULES,
                                     logical_to_spec, serve_rules)


class FakeMesh:
    """Stand-in with just axis_names/shape for rule resolution tests."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


SINGLE = FakeMesh({"data": 16, "model": 16})
MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_batch_spec_spans_pods():
    spec = logical_to_spec(("batch", "seq"), MULTI, TRAIN_RULES,
                           (256, 4096))
    assert spec == P(("pod", "data"))


def test_divisibility_fallback_batch_one():
    spec = logical_to_spec(("batch", "seq"), MULTI, TRAIN_RULES, (1, 1))
    assert spec == P()


def test_embed_fsdp_spans_pods_when_divisible():
    spec = logical_to_spec(("vocab", "embed"), MULTI, TRAIN_RULES,
                           (152064, 8192))
    assert spec == P("model", ("data", "pod"))


def test_embed_fallback_when_not_divisible_by_pods():
    # 8 % (16·2) != 0 → trims pod, then 8 % 16 != 0 → replicate
    spec = logical_to_spec(("embed",), MULTI, TRAIN_RULES, (8,))
    assert spec == P()


def test_serve_rules_replicate_embed_except_arctic():
    assert SERVE_RULES["embed"] is None
    arctic = serve_rules(get_config("arctic-480b"))
    assert arctic["embed"] == "data"
    dense = serve_rules(get_config("qwen2-72b"))
    assert dense["embed"] is None


def test_kv_seq_sharded_only_for_serving():
    assert TRAIN_RULES["kv_seq"] is None
    assert SERVE_RULES["kv_seq"] == "model"


def test_all_arch_param_dims_shard_on_production_mesh():
    """Every param leaf of every arch must shard (or cleanly fall back)
    on the 16×16 mesh — guards against new configs breaking divisibility."""
    from repro.models import build_model
    from repro.parallel.sharding import is_param_def
    for arch in ("qwen2-72b", "arctic-480b", "rwkv6-7b",
                 "recurrentgemma-2b", "musicgen-medium"):
        cfg = get_config(arch)
        defs = build_model(cfg).param_defs()
        for leaf in jax.tree.leaves(defs, is_leaf=is_param_def):
            spec = logical_to_spec(leaf.logical, SINGLE, TRAIN_RULES,
                                   leaf.shape)
            for dim, part in zip(leaf.shape, tuple(spec)):
                if part is None:
                    continue
                axes = (part,) if isinstance(part, str) else part
                size = int(np.prod([SINGLE.shape[a] for a in axes]))
                assert dim % size == 0, (arch, leaf.shape, spec)


# -- collective equivalence under shard_map (uses the real local device) --


def _local_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))


def test_ring_all_reduce_matches_psum_single_device():
    from repro.parallel.collectives import ring_all_reduce
    mesh = _local_mesh()
    x = jnp.arange(16.0).reshape(4, 4)
    f = jax.shard_map(lambda v: ring_all_reduce(v, "data"), mesh=mesh,
                      in_specs=P("data"), out_specs=P("data"))
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x))


def test_compressed_psum_error_bounded():
    from repro.parallel.collectives import compressed_psum
    mesh = _local_mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
    f = jax.shard_map(lambda v: compressed_psum(v, "data"), mesh=mesh,
                      in_specs=P(), out_specs=P())
    out = f(x)
    _, scale = quantize_int8(x)
    bound = float(jnp.max(scale)) / 2 + 1e-7
    assert float(jnp.max(jnp.abs(out - x))) <= bound
