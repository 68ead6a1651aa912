"""Fabric/mesh runtime specs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.parallel import Fabric, Precision


def test_fabric_defaults_all_devices():
    f = Fabric()
    assert f.world_size == len(jax.devices())
    assert dict(f.mesh.shape) == {"data": len(jax.devices())}


def test_fabric_device_subset():
    f = Fabric(devices=4)
    assert f.world_size == 4


def test_fabric_too_many_devices():
    with pytest.raises(ValueError):
        Fabric(devices=10**6)


def test_fabric_2d_mesh():
    f = Fabric(devices=8, mesh_axes=("data", "model"), mesh_shape=(4, 2))
    assert dict(f.mesh.shape) == {"data": 4, "model": 2}


def test_fabric_mesh_infer_axis():
    f = Fabric(devices=8, mesh_axes=("data", "model"), mesh_shape=(-1, 2))
    assert dict(f.mesh.shape) == {"data": 4, "model": 2}


def test_fabric_bad_mesh_shape():
    with pytest.raises(ValueError):
        Fabric(devices=8, mesh_axes=("data", "model"), mesh_shape=(3, 2))


def test_shard_batch_and_replicate():
    f = Fabric(devices=8)
    batch = {"x": np.arange(16, dtype=np.float32).reshape(16, 1)}
    sharded = f.shard_batch(batch)
    assert sharded["x"].sharding == f.batch_sharding
    params = f.replicate({"w": np.ones((3,), np.float32)})
    assert params["w"].sharding == f.replicated


def test_local_batch_size():
    f = Fabric(devices=8)
    assert f.local_batch_size(64) == 8
    with pytest.raises(ValueError):
        f.local_batch_size(63)


def test_precision_aliases():
    assert Precision("32-true").name == "fp32"
    assert Precision("bf16").name == "bf16-mixed"
    with pytest.raises(ValueError):
        Precision("fp16")


def test_precision_dtypes():
    p = Precision("bf16-mixed")
    assert p.param_dtype == jnp.float32
    assert p.compute_dtype == jnp.bfloat16
    t = Precision("bf16-true")
    assert t.param_dtype == jnp.bfloat16


def test_precision_cast_to_compute():
    p = Precision("bf16-mixed")
    tree = {"a": jnp.ones((2,), jnp.float32), "b": jnp.ones((2,), jnp.int32)}
    out = p.cast_to_compute(tree)
    assert out["a"].dtype == jnp.bfloat16
    assert out["b"].dtype == jnp.int32  # non-floating leaves untouched


def test_save_load_roundtrip(tmp_path):
    f = Fabric(devices=1)
    state = {"params": {"w": jnp.arange(4.0)}, "step": 7, "ratio": {"_prev": None}}
    path = str(tmp_path / "ckpt" / "state.ckpt")
    f.save(path, state)
    loaded = f.load(path)
    assert loaded["step"] == 7
    assert np.array_equal(loaded["params"]["w"], np.arange(4.0))
    assert loaded["ratio"]["_prev"] is None


def test_fabric_call_dispatches_to_callbacks():
    calls = []

    class CB:
        def on_checkpoint_coupled(self, fabric, **kw):
            calls.append(kw)

    f = Fabric(devices=1, callbacks=[CB()])
    f.call("on_checkpoint_coupled", ckpt_path="x", state={})
    assert calls == [{"ckpt_path": "x", "state": {}}]


def test_grad_pmean_matches_single_device():
    """DP gradient on an 8-way mesh == single-device gradient on full batch."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from sheeprl_tpu.parallel.shard_map import shard_map

    f = Fabric(devices=8)
    w = jnp.asarray([2.0, -1.0])
    x = np.random.default_rng(0).normal(size=(16, 2)).astype(np.float32)

    def loss(w, x):
        return jnp.mean(jnp.square(x @ w))

    full_grad = jax.grad(loss)(w, jnp.asarray(x))

    @partial(
        shard_map,
        mesh=f.mesh,
        in_specs=(P(), P("data")),
        out_specs=P(),
    )
    def dp_grad(w, x):
        return jax.lax.pmean(jax.grad(loss)(w, x), "data")

    np.testing.assert_allclose(jax.jit(dp_grad)(w, x), full_grad, rtol=1e-5)


@pytest.fixture()
def _cache_config():
    """Snapshot and restore the process-wide compile-cache settings."""
    names = (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_compilation_cache_unset_env_is_the_fixed_checkout_path(monkeypatch, _cache_config):
    """No JAX_COMPILATION_CACHE_DIR: the cache is ON at one fixed git-ignored
    path inside the checkout — not a temp name, not per pid, not per run."""
    import os

    from sheeprl_tpu.parallel import fabric as fabric_mod

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    want = os.path.join(repo, ".jax_cache")
    assert fabric_mod.REPO_COMPILATION_CACHE_DIR == want
    first = Fabric(devices=1).compilation_cache_dir
    assert first == want == Fabric(devices=2).compilation_cache_dir
    assert jax.config.jax_compilation_cache_dir == want and os.path.isdir(want)
    # on the CPU backend JAX's own thresholds stay (only an accelerator
    # persists every tiny program)
    assert jax.config.jax_persistent_cache_min_compile_time_secs > 0.0
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compilation_cache_set_env_is_left_alone(monkeypatch, tmp_path, _cache_config):
    """JAX_COMPILATION_CACHE_DIR set: that directory is the cache and Fabric
    never writes jax_compilation_cache_dir (JAX read the variable itself)."""
    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    writes = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: (writes.append(name), real_update(name, value))[1]
    )
    assert Fabric(devices=1).compilation_cache_dir == placed
    assert "jax_compilation_cache_dir" not in writes, writes


def test_fabric_accelerator_tpu_refuses_cpu_devices():
    """`tpu` is a demand, not a hint: on CPU devices it raises instead of
    training there; `auto` takes what is there and records what that was."""
    with pytest.raises(RuntimeError, match="'tpu' was requested but JAX found 'cpu'"):
        Fabric(devices=1, accelerator="tpu")
    assert Fabric(devices=1, accelerator="auto").platform == "cpu"
    assert Fabric(devices=1, accelerator="cpu").platform == "cpu"
