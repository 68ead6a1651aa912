"""The Pallas RSSM kernels and the replay ring's programs, compiled for a TPU
v5e that is described, not attached (on-chip-measurement guide, section 2,
third rehearsal).

Interpret mode cannot show what the chip's compiler refuses — VMEM limits,
block shapes, unaligned slices — and before this file the kernels had only
ever run interpreted, at toy widths. Here they compile at the Dreamer-V3
S/M/L/XL widths, forward and VJP, for every weight dtype: wherever the one
sizing verdict (``fits_vmem``) says the step fits, the compiler must accept
it and the program must hold the Mosaic custom call; wherever it says no, the
wrapper must refuse at trace time. Nothing runs, so nothing here is a result
or a time.

The topology is described inside a module-scoped fixture — never at import
(only one process may load the TPU library, and every xdist worker imports
every test file) — and all of these tests live in this one file so that one
worker loads it. A described-device compile cannot be read back from the
persistent cache, so the cache is off around them.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from benchmarks.pallas_gru_ab import SIZES
from sheeprl_tpu.ops.pallas_gru import fits_vmem, fused_recurrent_step, sharded_recurrent_step

# (dense_units, hidden) of the Dreamer-V3 size table the kernel A/B uses; the
# step's input is the 32x32 latent plus a 6-d action at every one of them
WIDTHS = {size: SIZES[size][1:] for size in ("S", "M", "L", "XL")}
IN_DIM = SIZES["S"][0]
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    had_log_dir = "TPU_LOG_DIR" in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        described = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    if not had_log_dir:
        os.environ.pop("TPU_LOG_DIR", None)


def _step_shapes(dense, hidden, dtype, batch, sharding, w2_sharding=None, batch_sharding=None):
    def sds(shape, dt, sh=sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    bsh = batch_sharding or sharding
    return (
        sds((batch, IN_DIM), jnp.float32, bsh),
        sds((batch, hidden), jnp.float32, bsh),
        sds((IN_DIM, dense), dtype),
        sds((dense,), dtype),
        sds((dense,), dtype),
        sds((dense,), dtype),
        sds((hidden + dense, 3 * hidden), dtype, w2_sharding or sharding),
        sds((3 * hidden,), dtype),
        sds((3 * hidden,), dtype),
    )


def _forward_and_vjp(step):
    """The two programs training dispatches: the step, and its value+grad
    with respect to every input (value kept, so the forward kernel stays)."""
    return {
        "fwd": jax.jit(step),
        "vjp": jax.jit(jax.value_and_grad(lambda *a: jnp.sum(step(*a)), argnums=tuple(range(9)))),
    }


@pytest.mark.parametrize("what", ["fwd", "vjp"])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("size", list(WIDTHS))
def test_fused_step_compiles_where_the_gate_says_it_fits(topo, size, dtype, what):
    from jax.sharding import SingleDeviceSharding

    dense, hidden = WIDTHS[size]
    shapes = _step_shapes(dense, hidden, DTYPES[dtype], 16, SingleDeviceSharding(topo.devices[0]))
    program = _forward_and_vjp(fused_recurrent_step)[what]
    if fits_vmem(IN_DIM, dense, hidden, DTYPES[dtype]):
        assert "tpu_custom_call" in program.lower(*shapes).compile().as_text()
    else:
        with pytest.raises(ValueError, match="too large for VMEM-resident kernel"):
            program.lower(*shapes)


def test_fused_step_compiles_at_the_imagination_batch(topo):
    """Behaviour learning calls the step on batch x sequence = 1024 rows:
    four 256-row tiles over the same resident weights."""
    from jax.sharding import SingleDeviceSharding

    dense, hidden = WIDTHS["S"]
    shapes = _step_shapes(dense, hidden, jnp.float32, 16 * 64, SingleDeviceSharding(topo.devices[0]))
    for program in _forward_and_vjp(fused_recurrent_step).values():
        assert "tpu_custom_call" in program.lower(*shapes).compile().as_text()


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("model_shards", [4, 2])
@pytest.mark.parametrize("size", ["L", "XL"])
def test_sharded_step_compiles_on_the_four_chip_mesh(topo, size, model_shards, dtype):
    """The branch ``fused: auto`` takes on a ``model`` axis, on a (data,
    model) mesh built from the described devices: the per-device W2 slice
    in VMEM, LayerNorm statistics psum'd, the new state all-gathered."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    dense, hidden = WIDTHS[size]
    mesh = Mesh(np.asarray(topo.devices).reshape(4 // model_shards, model_shards), ("data", "model"))
    shapes = _step_shapes(
        dense,
        hidden,
        DTYPES[dtype],
        16,
        NamedSharding(mesh, P()),
        w2_sharding=NamedSharding(mesh, P(None, "model")),
        batch_sharding=NamedSharding(mesh, P("data")),
    )
    programs = _forward_and_vjp(lambda *a: sharded_recurrent_step(*a, mesh=mesh, data_axis="data"))
    if fits_vmem(IN_DIM, dense, hidden, DTYPES[dtype], model_shards):
        for program in programs.values():
            hlo = program.lower(*shapes).compile().as_text()
            assert "tpu_custom_call" in hlo and "all-gather" in hlo and "all-reduce" in hlo
    else:
        for program in programs.values():
            with pytest.raises(ValueError, match="too large for the VMEM-resident kernel"):
                program.lower(*shapes)


@pytest.mark.parametrize("ring", list(chip_smoke.RINGS))
def test_ring_programs_address_the_ring_in_the_layout_it_is_stored_in(topo, ring):
    """The TPU's side of ``test_device_buffer.py``'s guard, the phase
    ``chip_smoke.py`` runs on the attached chip: at the benchmark cells' ring
    sizes and at the walker recipe's own 500,000 frames, ``ring_write`` and
    ``ring_gather_sequences`` compile for a 16 GB chip, keep the ring in the
    one layout the runtime stores it in (no instruction of its shape but the
    in-place row updates) and ask for under 64 MB of temporaries. Stored as
    ``[..., 64, 64, 3]`` each program copied all 3.07 GB per call, and the
    recipe's size asked for 17.19 GB."""
    chip_smoke.phase_ring_programs({ring: chip_smoke.RINGS[ring]}, max_temp_bytes=64 << 20, device=topo.devices[0])
