"""repro.compat mesh/sharding helpers and the kernel backend registry
(repro.kernels.backend)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.kernels import backend


# ------------------------------------------------------------------ make_mesh
def test_make_mesh_native():
    mesh = compat.make_mesh((1,), ("data",))
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == 1
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)


def test_make_mesh_new_spelling_passes_axis_types(monkeypatch):
    """compat.make_mesh hands jax.make_mesh one AxisType per axis."""
    recorded = {}

    def fake_make_mesh(axis_shapes, axis_names, *, devices=None,
                       axis_types=None):
        recorded.update(shapes=axis_shapes, names=axis_names,
                        axis_types=axis_types)
        return "fake-mesh"

    monkeypatch.setattr(jax, "make_mesh", fake_make_mesh)
    assert compat.make_mesh((2, 4), ("data", "model")) == "fake-mesh"
    auto = jax.sharding.AxisType.Auto
    assert recorded == {"shapes": (2, 4), "names": ("data", "model"),
                        "axis_types": (auto, auto)}
    assert compat.make_mesh((1,), ("x",), kind="explicit") == "fake-mesh"
    assert recorded["axis_types"] == (jax.sharding.AxisType.Explicit,)


# --------------------------------------------------------------- current_mesh
def test_current_mesh_tracks_context():
    assert compat.current_mesh() is None
    mesh = compat.make_mesh((1,), ("data",))
    with mesh:
        got = compat.current_mesh()
        assert got is not None and got.axis_names == ("data",)
    assert compat.current_mesh() is None


# ------------------------------------------------------ sharding constructors
def test_named_sharding_accepts_parts_and_spec():
    mesh = compat.make_mesh((1,), ("data",))
    a = compat.named_sharding(mesh, "data", None)
    b = compat.named_sharding(mesh, P("data", None))
    assert a.spec == b.spec == P("data", None)


def test_replicated_like_mirrors_tree():
    mesh = compat.make_mesh((1,), ("data",))
    tree = {"a": jnp.ones((2,)), "b": {"c": jnp.ones((3,))}}
    sh = compat.replicated_like(mesh, tree)
    assert set(sh) == {"a", "b"}
    assert sh["b"]["c"].spec == P()


# ----------------------------------------------------------- backend registry
def test_backend_registry_has_all_ops():
    import repro.kernels.ops  # noqa: F401  (registration side effect)
    assert {"attention", "ssm_scan", "retention"} <= set(backend.registered())
    for op in ("attention", "ssm_scan", "retention"):
        assert backend.available_backends(op) == ("tpu", "interpret", "xla")


def test_backend_resolution_order(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    assert backend.resolve_backend("interpret") == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert backend.resolve_backend() == "interpret"
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    expected = "tpu" if jax.default_backend() == "tpu" else "xla"
    assert backend.resolve_backend() == expected
    with pytest.raises(ValueError):
        backend.resolve_backend("cuda")
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "cuda")
    with pytest.raises(ValueError):
        backend.resolve_backend()


def test_backend_dispatch_agrees_across_backends(monkeypatch):
    """attention via xla and interpret backends must agree numerically."""
    import repro.kernels.ops as ops
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
               for _ in range(3))
    y_xla = backend.dispatch("attention", q, k, v, causal=True, backend="xla")
    y_int = backend.dispatch("attention", q, k, v, causal=True,
                             backend="interpret")
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_int),
                               rtol=2e-5, atol=2e-5)
    # the public entry point honors the env override
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla")
    np.testing.assert_allclose(np.asarray(ops.attention(q, k, v)),
                               np.asarray(y_xla), rtol=0, atol=0)


def test_backend_missing_impl_falls_back_to_xla(monkeypatch):
    backend.register("_probe_op", xla=lambda x: x + 1)
    try:
        assert backend.dispatch("_probe_op", 1, backend="interpret") == 2
        assert backend.dispatch("_probe_op", 1, backend="tpu") == 2
        with pytest.raises(KeyError):
            backend.dispatch("_unregistered_op", 1)
        with pytest.raises(ValueError):
            backend.register("_probe_op", cuda=lambda x: x)
    finally:
        backend._REGISTRY.pop("_probe_op", None)
