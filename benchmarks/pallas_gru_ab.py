"""On-chip A/B of the Pallas fused RSSM step vs the pure-JAX/flax cell.
Not measured on the current code.

Measures a 64-step ``lax.scan`` over the recurrent body — exactly how the
train step consumes it — at the Dreamer-V3 model sizes, both directions
(forward-only and forward+backward through ``jax.grad``).

Two regimes per size, selected by ``--layouts dxm`` (data×model):

- ``m == 1`` (replicated): ``fused_recurrent_step`` (whole-step kernel,
  weights + tile in VMEM) vs ``reference_step`` under plain jit — the A/B
  behind ``fused: auto`` resolving to the flax cell on a replicated layout.
- ``m > 1`` (model-sharded): ``sharded_recurrent_step`` (per-device
  ``[H+D, 3H/m]`` W2 slice pinned in VMEM across the scan, LN stats psum'd,
  one all-gather per step) vs the GSPMD baseline (``reference_step`` jitted
  with W2 committed to ``P(None, "model")`` — XLA inserts the collectives
  and re-streams each shard from HBM every timestep). This is the layout
  the 2-D fused superstep trains with; sweep ``--batches`` to the knee that
  ``benchmarks/gru_roofline.py`` measures.

Run on the TPU: ``python benchmarks/pallas_gru_ab.py --sizes L,XL
--layouts 1x4,2x4 --batches 64,128,256,304 --dtype bf16``.
Off the TPU the compiled kernel cannot run: pass ``--interpret`` for a CPU
smoke run, which times the interpreter and nothing else.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.ops.pallas_gru import (
    fits_vmem,
    fused_recurrent_step,
    reference_step,
    sharded_recurrent_step,
)

# (label, x_dim, dense_units, hidden) — stoch 32x32 + action appended, per
# the DV3 size table; XS uses the smaller latent
SIZES = {
    "XS": (4 * 4 + 6, 256, 256),
    "S": (32 * 32 + 6, 512, 512),
    "M": (32 * 32 + 6, 640, 1024),
    "L": (32 * 32 + 6, 768, 2048),
    "XL": (32 * 32 + 6, 1024, 4096),
}
T = 64
REPEAT = 10  # scan length multiplier so compute >> one dispatch round trip


def _params(key, x_dim, dense, hidden, dtype):
    ks = jax.random.split(key, 4)
    scale = 0.02
    return dict(
        w1=(jax.random.normal(ks[0], (x_dim, dense)) * scale).astype(dtype),
        b1=jnp.zeros((dense,), dtype),
        g1=jnp.ones((dense,), dtype),
        be1=jnp.zeros((dense,), dtype),
        w2=(jax.random.normal(ks[1], (hidden + dense, 3 * hidden)) * scale).astype(dtype),
        g2=jnp.ones((3 * hidden,), dtype),
        be2=jnp.zeros((3 * hidden,), dtype),
    )


def _scan_fn(step, p):
    def run(h0, xs):
        def body(h, x):
            h = step(x, h, p["w1"], p["b1"], p["g1"], p["be1"], p["w2"], p["g2"], p["be2"])
            return h, ()

        h, _ = jax.lax.scan(body, h0, xs)
        return h.sum()

    return run


def _time(fn, *args):
    out = fn(*args)
    np.asarray(out)  # compile + settle
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(fn(*args))
        times.append(time.perf_counter() - t0)
    return min(times)


def _jit_pair(step, p):
    fwd = jax.jit(_scan_fn(step, p))
    grad = jax.jit(jax.grad(_scan_fn(step, p), argnums=0))
    return fwd, grad


def _run_pair(step_a, step_b, p, h0, xs):
    """(fwd_a, bwd_a, fwd_b, bwd_b) wall times for one 64*REPEAT-step scan."""
    fwd_a, grad_a = _jit_pair(step_a, p)
    fwd_b, grad_b = _jit_pair(step_b, p)
    return [
        _time(fwd_a, h0, xs),
        _time(grad_a, h0, xs),
        _time(fwd_b, h0, xs),
        _time(grad_b, h0, xs),
    ]


def _report(label, layout, batch, dtype, pf, pg, ff, fg):
    d, m = layout
    scale = 1e3 / REPEAT  # ms per 64-step scan
    print(
        f"{label} {d}x{m} B={batch} {jnp.dtype(dtype).name}: "
        f"fwd pallas {pf * scale:.2f} ms vs xla {ff * scale:.2f} ms ({ff / pf:.2f}x); "
        f"fwd+bwd pallas {pg * scale:.2f} ms vs xla {fg * scale:.2f} ms ({fg / pg:.2f}x)"
    )


def run_case(label, batch, layout, dtype, interpret):
    x_dim, dense, hidden = SIZES[label]
    d, m = layout
    key = jax.random.fold_in(jax.random.PRNGKey(0), hash((label, batch, d, m)) % (1 << 30))
    # distinct streams for the params and the input batch — drawing both
    # from the same key would correlate them (and flags JX01)
    p_key, x_key = jax.random.split(key)

    if m == 1:
        if not fits_vmem(x_dim, dense, hidden, dtype):
            print(f"{label} {d}x{m}: exceeds the replicated-kernel VMEM budget, skipped")
            return
        p = _params(p_key, x_dim, dense, hidden, dtype)
        h0 = jnp.zeros((batch, hidden))
        xs = jax.random.normal(x_key, (T * REPEAT, batch, x_dim))
        def pallas_step(*a):
            return fused_recurrent_step(*a, interpret=interpret)

        pf, pg, ff, fg = _run_pair(pallas_step, reference_step, p, h0, xs)
        _report(label, layout, batch, dtype, pf, pg, ff, fg)
        return

    n_dev = d * m
    if n_dev > len(jax.devices()):
        print(f"{label} {d}x{m}: needs {n_dev} devices, have {len(jax.devices())}; skipped")
        return
    if hidden % m != 0:
        print(f"{label} {d}x{m}: hidden {hidden} not divisible by model={m}; skipped")
        return
    if not fits_vmem(x_dim, dense, hidden, dtype, model_shards=m):
        print(f"{label} {d}x{m}: per-shard slice exceeds the VMEM budget, skipped")
        return
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]).reshape(d, m), ("data", "model"))
    data_axis = "data" if d > 1 else None
    p = _params(p_key, x_dim, dense, hidden, dtype)
    # commit the GSPMD-baseline placements once: W2 model-sharded, the rest
    # replicated, batch over the data axis — both arms consume the same arrays
    p = {
        k: jax.device_put(v, NamedSharding(mesh, P(None, "model") if k == "w2" else P()))
        for k, v in p.items()
    }
    h0 = jax.device_put(jnp.zeros((batch, hidden)), NamedSharding(mesh, P(data_axis)))
    xs = jax.device_put(
        jax.random.normal(x_key, (T * REPEAT, batch, x_dim)),
        NamedSharding(mesh, P(None, data_axis)),
    )

    def sharded_step(*a):
        return sharded_recurrent_step(
            *a, mesh=mesh, data_axis=data_axis, use_pallas=True, interpret=interpret
        )

    with mesh:
        pf, pg, ff, fg = _run_pair(sharded_step, reference_step, p, h0, xs)
    _report(label, layout, batch, dtype, pf, pg, ff, fg)


def _parse_layouts(spec):
    out = []
    for item in spec.split(","):
        d, _, m = item.strip().partition("x")
        out.append((int(d), int(m)))
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default="XS,S,M", help=f"comma list from {list(SIZES)}")
    ap.add_argument("--layouts", default="1x1", help="comma list of dxm (data x model), e.g. 1x1,2x4")
    ap.add_argument("--batches", default="16", help="comma list of GLOBAL batch sizes to sweep")
    ap.add_argument("--dtype", default="fp32", choices=("fp32", "bf16"), help="weight storage dtype")
    ap.add_argument(
        "--interpret", action="store_true", help="pallas interpreter mode (CPU smoke runs only)"
    )
    args = ap.parse_args(argv)
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    interpret = args.interpret
    print(
        f"backend={jax.default_backend()} devices={len(jax.devices())} "
        f"scan length={T * REPEAT} interpret={interpret}"
    )
    for label in [s.strip() for s in args.sizes.split(",")]:
        for layout in _parse_layouts(args.layouts):
            for batch in [int(b) for b in args.batches.split(",")]:
                run_case(label, batch, layout, dtype, interpret)


if __name__ == "__main__":
    main()
