"""Logical-axis partitioner: fallback semantics on synthetic meshes."""
import numpy as np
import pytest
import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel import partitioner as pt


class FakeMesh:
    """Duck-typed mesh (axis_names + devices.shape) for assignment tests."""
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape, object)


M = FakeMesh((2, 16, 16), ("pod", "data", "model"))
SP = FakeMesh((16, 16), ("data", "model"))


def spec(logical, shape, mesh=M, rules=None):
    return pt.assign_spec(logical, shape, mesh, rules or pt.DEFAULT_RULES)


def test_batch_pod_data():
    assert spec(("batch", "seq"), (256, 4096)) == P(("pod", "data"), None)


def test_batch_fallback_data_only():
    # batch=16 not divisible by pod*data=32 -> falls to data
    assert spec(("batch", "seq"), (16, 128)) == P("data", None)


def test_batch_indivisible_unsharded():
    assert spec(("batch", "seq"), (1, 524288)) == P(None, None)


def test_kv_cache_head_parallel_vs_seq_parallel():
    # gemma: kv=16 divisible -> head-parallel cache
    s = spec(("layers", "batch", "kv_heads", "kv_seq", "head_dim"),
             (28, 128, 16, 32768, 256))
    assert s == P(None, ("pod", "data"), "model", None, None)
    # yi: kv=4 not divisible -> sequence-parallel cache (flash-decoding)
    s = spec(("layers", "batch", "kv_heads", "kv_seq", "head_dim"),
             (48, 128, 4, 32768, 128))
    assert s == P(None, ("pod", "data"), None, "model", None)


def test_axis_used_once_per_tensor():
    # after heads takes model, kv_seq cannot also take it
    s = spec(("heads", "kv_seq"), (16, 32768))
    assert s == P("model", None)


def test_missing_axis_skipped():
    s = spec(("batch",), (256,), mesh=SP)
    assert s == P("data")


def test_override_rules():
    rules = pt.merge_rules(pt.DEFAULT_RULES, (
        ("experts", (("pod", "model"), ("model",))),
        ("expert_mlp", (("data",),)),
    ))
    s = pt.assign_spec(("layers", "experts", "embed", "expert_mlp"),
                       (61, 384, 7168, 2048), M, rules)
    assert s == P(None, ("pod", "model"), None, "data")
    # single-pod mesh: (pod, model) unavailable -> falls to model
    s = pt.assign_spec(("experts", "embed", "expert_mlp"),
                       (384, 7168, 2048), SP, rules)
    assert s == P("model", None, "data")


def test_tree_shardings_real_mesh():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    axes = {"w": ("embed", "mlp"), "b": ("mlp",), "scalar": None}
    abstract = {"w": jax.ShapeDtypeStruct((4, 8), np.float32),
                "b": jax.ShapeDtypeStruct((8,), np.float32),
                "scalar": jax.ShapeDtypeStruct((), np.float32)}
    sh = pt.tree_shardings(axes, abstract, mesh, pt.DEFAULT_RULES)
    assert sh["w"].spec == P(None, "model")
    assert sh["scalar"].spec == P()


def test_rank_mismatch_raises():
    with pytest.raises(ValueError):
        spec(("batch",), (4, 4))


def test_host_mesh_rejects_model_degree_that_does_not_divide():
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    assert make_host_mesh(n).devices.shape == (1, n)
    with pytest.raises(ValueError, match="does not divide"):
        make_host_mesh(n + 1)
