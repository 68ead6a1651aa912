"""Pallas fused RSSM step vs the flax reference path.

Runs the kernel in interpreter mode (CPU test mesh); on a real TPU the same
code path compiles to Mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.ops.pallas_gru import (
    fits_vmem,
    fused_recurrent_step,
    reference_step,
    resolve_backend,
    sharded_recurrent_step,
)


def _random_args(key, batch=5, in_dim=12, dense=16, hidden=8):
    ks = jax.random.split(key, 9)
    x = jax.random.normal(ks[0], (batch, in_dim), jnp.float32)
    h = jax.random.normal(ks[1], (batch, hidden), jnp.float32)
    w1 = jax.random.normal(ks[2], (in_dim, dense), jnp.float32) * 0.3
    b1 = jax.random.normal(ks[3], (dense,), jnp.float32) * 0.1
    g1 = 1.0 + 0.1 * jax.random.normal(ks[4], (dense,), jnp.float32)
    be1 = 0.1 * jax.random.normal(ks[5], (dense,), jnp.float32)
    w2 = jax.random.normal(ks[6], (hidden + dense, 3 * hidden), jnp.float32) * 0.3
    g2 = 1.0 + 0.1 * jax.random.normal(ks[7], (3 * hidden,), jnp.float32)
    be2 = 0.1 * jax.random.normal(ks[8], (3 * hidden,), jnp.float32)
    return x, h, w1, b1, g1, be1, w2, g2, be2


@pytest.mark.parametrize("batch", [1, 5, 16])
def test_fused_matches_reference(batch):
    args = _random_args(jax.random.PRNGKey(0), batch=batch)
    got = fused_recurrent_step(*args, interpret=True)
    want = reference_step(*args)
    assert got.shape == (batch, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_fused_gradients_match_reference():
    args = _random_args(jax.random.PRNGKey(1))

    def loss_fused(*a):
        return jnp.sum(jnp.square(fused_recurrent_step(*a, interpret=True)))

    def loss_ref(*a):
        return jnp.sum(jnp.square(reference_step(*a)))

    grads_fused = jax.grad(loss_fused, argnums=tuple(range(9)))(*args)
    grads_ref = jax.grad(loss_ref, argnums=tuple(range(9)))(*args)
    for gf, gr in zip(grads_fused, grads_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=1e-4, rtol=1e-4)


def test_fused_matches_flax_recurrent_model():
    """Identical math to the flax RecurrentModel (Dense→LN→SiLU→LN-GRU)."""
    from sheeprl_tpu.algos.dreamer_v3.agent import RecurrentModel

    batch, in_dim, dense, hidden = 4, 10, 12, 8
    model = RecurrentModel(hidden, dense)
    x = jax.random.normal(jax.random.PRNGKey(2), (batch, in_dim), jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (batch, hidden), jnp.float32)
    params = model.init(jax.random.PRNGKey(4), x, h)
    want = model.apply(params, x, h)

    p = params["params"]
    got = fused_recurrent_step(
        x,
        h,
        p["Dense_0"]["kernel"],
        p["Dense_0"]["bias"],
        p["LayerNorm_0"]["LayerNorm_0"]["scale"],
        p["LayerNorm_0"]["LayerNorm_0"]["bias"],
        p["LayerNormGRUCell_0"]["Dense_0"]["kernel"],
        p["LayerNormGRUCell_0"]["LayerNorm_0"]["LayerNorm_0"]["scale"],
        p["LayerNormGRUCell_0"]["LayerNorm_0"]["LayerNorm_0"]["bias"],
        eps1=1e-3,
        eps2=1e-5,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_checkpoint_interchange_with_flax_module():
    """FusedRecurrentModel declares the SAME param tree as RecurrentModel, so
    checkpoints restore across the fused/flax backend flag — and the same
    params give the same output."""
    from sheeprl_tpu.algos.dreamer_v3.agent import FusedRecurrentModel, RecurrentModel

    flax_model = RecurrentModel(8, 12)
    fused_model = FusedRecurrentModel(8, 12, interpret=True)
    x = jax.random.normal(jax.random.PRNGKey(10), (4, 10), jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(11), (4, 8), jnp.float32)
    flax_params = flax_model.init(jax.random.PRNGKey(12), x, h)
    fused_params = fused_model.init(jax.random.PRNGKey(12), x, h)
    assert jax.tree_util.tree_structure(flax_params) == jax.tree_util.tree_structure(fused_params)
    # flax-trained params drop into the fused module (and vice versa)
    np.testing.assert_allclose(
        np.asarray(fused_model.apply(flax_params, x, h)),
        np.asarray(flax_model.apply(flax_params, x, h)),
        atol=1e-5,
        rtol=1e-5,
    )


def test_fused_module_trains():
    """FusedRecurrentModel initializes, applies, and has finite grads."""
    from sheeprl_tpu.algos.dreamer_v3.agent import FusedRecurrentModel

    model = FusedRecurrentModel(8, 12, interpret=True)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 10), jnp.float32)
    h = jnp.zeros((3, 8), jnp.float32)
    params = model.init(jax.random.PRNGKey(6), x, h)
    out = model.apply(params, x, h)
    assert out.shape == (3, 8)

    def loss(p):
        return jnp.sum(jnp.square(model.apply(p, x, h)))

    grads = jax.grad(loss)(params)
    leaves = jax.tree_util.tree_leaves(grads)
    assert leaves and all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in leaves)


def test_resolve_backend_policy():
    # off: never pallas
    assert resolve_backend(False, 64, 64, 64) is False
    assert resolve_backend("flax", 64, 64, 64) is False
    # auto on a replicated layout: the flax cell, on every backend
    assert resolve_backend("auto", 64, 64, 64) is False
    # forced: pallas — and never an inferred interpreter (the caller of the
    # kernel says interpret=True, or the kernel compiles for the device)
    assert resolve_backend("pallas", 64, 64, 64) is True
    # forced but too large for VMEM: an error, not a silent flax cell
    with pytest.raises(ValueError, match="exceeds the VMEM-resident kernel's budget"):
        resolve_backend("pallas", 4096, 8192, 8192)
    with pytest.raises(ValueError, match="unknown fused-recurrent mode"):
        resolve_backend("bogus", 64, 64, 64)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("size", ["S", "M", "L", "XL"])
def test_gate_and_kernel_agree_on_vmem(size, dtype):
    """One sizing function, one answer: wherever ``fits_vmem`` says yes at
    the weights' storage dtype the kernel wrapper traces, and wherever it
    says no the wrapper refuses — the M bf16 case used to pass the gate and
    die in the kernel, which sized VMEM as fp32."""
    from benchmarks.pallas_gru_ab import SIZES  # the Dreamer-V3 size table

    (in_dim, dense, hidden), batch = SIZES[size], 16
    shapes = [
        jax.ShapeDtypeStruct(s, d)
        for s, d in (
            ((batch, in_dim), jnp.float32),
            ((batch, hidden), jnp.float32),
            ((in_dim, dense), dtype),
            ((dense,), dtype),
            ((dense,), dtype),
            ((dense,), dtype),
            ((hidden + dense, 3 * hidden), dtype),
            ((3 * hidden,), dtype),
            ((3 * hidden,), dtype),
        )
    ]
    trace = lambda: jax.eval_shape(lambda *a: fused_recurrent_step(*a, interpret=True), *shapes)  # noqa: E731
    if fits_vmem(in_dim, dense, hidden, dtype):
        assert trace().shape == (batch, hidden)
        assert resolve_backend("pallas", in_dim, dense, hidden, dtype) is True
    else:
        with pytest.raises(ValueError, match="too large for VMEM-resident kernel"):
            trace()
        with pytest.raises(ValueError, match="exceeds the VMEM-resident kernel's budget"):
            resolve_backend("pallas", in_dim, dense, hidden, dtype)


def test_fused_bf16_weights_stay_bf16_in_the_kernel():
    """bf16-stored weights reach the kernel as bf16 (that is what the gate
    sized) and the result stays close to the fp32 reference."""
    args = _random_args(jax.random.PRNGKey(3), batch=8, in_dim=16, dense=16, hidden=8)
    cast = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
    x, h, *weights = args
    got = fused_recurrent_step(x, h, *[cast(w) for w in weights], interpret=True)
    want = reference_step(x, h, *[cast(w).astype(jnp.float32) for w in weights])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-2)


def test_fits_vmem_regimes():
    assert fits_vmem(1536, 512, 512)  # Dreamer-V3 S
    assert not fits_vmem(8192, 8192, 8192)


def test_tile_bytes_dtype_and_shard_accounting():
    """ISSUE-14 satellite: the VMEM budget accounts weights at their STORAGE
    dtype (the old 4-byte hardcode under-admitted bf16 runs) and divides W2
    by the model-shard count. The L 4-shard case is the verdict flip: over
    budget in fp32, within it in bf16."""
    from sheeprl_tpu.ops.pallas_gru import _tile_bytes

    in_dim, dense, hidden = 1536, 768, 2048  # Dreamer-V3 L
    fp32 = _tile_bytes(in_dim, dense, hidden, 8, jnp.float32, 4)
    bf16 = _tile_bytes(in_dim, dense, hidden, 8, jnp.bfloat16, 4)
    assert bf16 < fp32  # activations stay fp32; only the weight term halves
    assert not fits_vmem(in_dim, dense, hidden, jnp.float32, model_shards=4)
    assert fits_vmem(in_dim, dense, hidden, jnp.bfloat16, model_shards=4)
    # XL per-shard slice on a 16-way model axis fits in bf16
    assert fits_vmem(32 * 32 + 6, 1024, 4096, jnp.bfloat16, model_shards=16)
    # legacy positional calls (no dtype, no shards) still mean fp32 x 1
    assert _tile_bytes(1536, 512, 512, 8) == _tile_bytes(1536, 512, 512, 8, jnp.float32, 1)


def test_resolve_backend_model_shards():
    """auto at model_shards > 1 adopts the sharded kernel exactly when
    on-TPU and the per-shard slice fits VMEM (the ISSUE-14 adoption hook);
    forced pallas honors the sharded budget the same way."""
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_backend("auto", 32 * 32 + 6, 1024, 4096, jnp.bfloat16, 16) is on_tpu
    # sharded but the slice does NOT fit: stays flax
    assert resolve_backend("auto", 8192, 8192, 8192, jnp.float32, 2) is False
    assert resolve_backend("pallas", 1536, 768, 2048, jnp.bfloat16, 4) is True
    with pytest.raises(ValueError):  # the L fp32 4-shard flip case does not fit
        resolve_backend("pallas", 1536, 768, 2048, jnp.float32, 4)


# --------------------------------------------------------------------------
# model-sharded step (interpret mode on the session's 8 virtual CPU devices)
# --------------------------------------------------------------------------
def _mesh_2d():
    from jax.sharding import Mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    return Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ("data", "model"))


@pytest.mark.parametrize("use_pallas,data_axis", [(True, "data"), (False, None)])
def test_sharded_step_matches_reference(use_pallas, data_axis):
    """sharded_recurrent_step (per-shard W2 slice + psum'd LN stats + tiled
    all_gather) reproduces the replicated reference on a (2 data x 4 model)
    mesh — with and without the pallas projection, replicated and
    batch-sharded."""
    mesh = _mesh_2d()
    args = _random_args(jax.random.PRNGKey(7), batch=4)
    got = sharded_recurrent_step(
        *args, mesh=mesh, data_axis=data_axis, use_pallas=use_pallas, interpret=True
    )
    want = reference_step(*args)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_sharded_step_gradients_match_reference():
    """The custom-vjp projection backward (three plain matmuls) and the
    collective-threaded gate math give the same gradients as the reference
    for every input."""
    mesh = _mesh_2d()
    args = _random_args(jax.random.PRNGKey(8), batch=4)

    def loss_sharded(*a):
        out = sharded_recurrent_step(
            *a, mesh=mesh, data_axis="data", use_pallas=True, interpret=True
        )
        return jnp.sum(jnp.square(out))

    def loss_ref(*a):
        return jnp.sum(jnp.square(reference_step(*a)))

    grads_sharded = jax.grad(loss_sharded, argnums=tuple(range(9)))(*args)
    grads_ref = jax.grad(loss_ref, argnums=tuple(range(9)))(*args)
    for gs, gr in zip(grads_sharded, grads_ref):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gr), atol=1e-4, rtol=1e-4)


def test_sharded_step_rejects_indivisible_hidden():
    mesh = _mesh_2d()
    args = _random_args(jax.random.PRNGKey(9), batch=4, hidden=6)  # 6 % 4 != 0
    with pytest.raises(ValueError, match="must divide"):
        sharded_recurrent_step(*args, mesh=mesh, interpret=True)
