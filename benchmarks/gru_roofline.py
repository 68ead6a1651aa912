"""Roofline probe for the RSSM scan's weight-streaming bound.

Hypothesis to test (not measured on the current code): at the recipe batch
(16) the GRU scan re-streams the joint projection matrix ``W2
[H+D, 3H]`` from HBM every timestep — 126 MB (bf16) per step at XL — and a
VMEM-resident kernel cannot fix it because W2 alone exceeds the ~16 MB/core
VMEM at L/XL (``ops/pallas_gru.py fits_vmem``).

This probe makes that a measurement. For each size it times, on the
attached accelerator:

1. ``scan-matmul``: ``h_{t+1} = tanh(h_t @ W)`` over T steps — the isolated
   sequential recurrent matmul, nothing else. Roofline prediction:
   ``T * max(bytes(W) / HBM_BW, flops / PEAK)``. When the measured time
   tracks the bytes term, the scan is weight-bound and no same-batch kernel
   can beat it on one core.
2. the same scan at growing batch sizes — arithmetic intensity rises with B,
   so the measured time should stay FLAT until the compute term crosses the
   bytes term (the roofline knee), then grow linearly. The knee batch is the
   per-device batch at which L/XL stop being bandwidth-bound — the number
   that justifies the multi-chip recipe (8-way DP at per-device batch >=
   knee).

Timing uses a chained-step estimator (N dispatches chained on-device, outputs
referenced, one materializing fetch, the tiny-op round trip subtracted). The
roofline's peaks come from ``utils/profiler.py`` by ``device_kind``; a device
that is not in that table is an error, not a default.

Usage (needs a device the peaks table knows — a TPU):
    python benchmarks/gru_roofline.py --sizes M L XL
    python benchmarks/gru_roofline.py --sizes XL --batches 16 32 64 128 256
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# H = recurrent_state_size, D = dense_units (configs/algo/dreamer_v3_{S,M,L}.yaml
# and the XL == base config)
DIMS = {
    "S": (512, 512),
    "M": (1024, 640),
    "L": (2048, 768),
    "XL": (4096, 1024),
}


def chained_seconds(fn, args, chain: int, repeat: int, rtt: float) -> float:
    """Device-busy seconds per call: chain ``chain`` dependent dispatches,
    fetch one scalar, subtract the dispatch round trip."""
    import jax
    import jax.numpy as jnp

    out = fn(*args)
    np.asarray(jnp.ravel(out[0] if isinstance(out, tuple) else out)[0].astype(jnp.float32))
    best = float("inf")
    for _ in range(repeat):
        keep = []
        t0 = time.perf_counter()
        h = args[0]
        for _ in range(chain):
            h = fn(h, *args[1:])
            if isinstance(h, tuple):
                h = h[0]
            keep.append(h)
        np.asarray(jnp.ravel(keep[-1])[0].astype(jnp.float32))
        dt = time.perf_counter() - t0
        best = min(best, max(dt - rtt, 1e-9) / chain)
    return best


def probe_size(size: str, batches, T: int, chain: int, repeat: int, hbm_bw: float, peak: float):
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.utils.profiler import tiny_op_rtt_seconds

    H, D = DIMS[size]
    rtt = tiny_op_rtt_seconds()
    # the REAL joint projection shape: [h, feat] @ W2 with W2 [H+D, 3H]
    # (ops/pallas_gru.py reference_step) — XL: (4096+1024)x12288 bf16 = 126 MB
    W = jnp.asarray(np.random.default_rng(0).normal(size=(H + D, 3 * H)) * 0.01, jnp.bfloat16)
    w_bytes = W.size * 2

    records = []
    for B in batches:
        h0 = jnp.zeros((B, H), jnp.bfloat16)
        feat = jnp.zeros((B, D), jnp.bfloat16)

        @jax.jit
        def scan_matmul(h, feat=feat, W=W):
            # GRU-shaped recurrence: the full [H+D, 3H] matrix is genuinely
            # consumed every step (reset/cand/update gates on the joint
            # [h, feat] row), so XLA cannot hoist or slice it — exactly the
            # fused step's streaming pattern
            def step(h, _):
                p = jnp.dot(
                    jnp.concatenate([h, feat], axis=-1), W, preferred_element_type=jnp.float32
                )
                H_ = h.shape[1]
                u = jax.nn.sigmoid(p[:, 2 * H_ :] - 1.0)
                c = jnp.tanh(jax.nn.sigmoid(p[:, :H_]) * p[:, H_ : 2 * H_])
                return (u * c + (1 - u) * h.astype(jnp.float32)).astype(jnp.bfloat16), ()

            out, _ = jax.lax.scan(step, h, None, length=T)
            return out

        measured = chained_seconds(scan_matmul, (h0,), chain, repeat, rtt)
        flops = 2 * B * (H + D) * 3 * H * T
        bytes_term = w_bytes * T / hbm_bw
        compute_term = flops / peak
        pred = max(bytes_term, compute_term)
        records.append(
            {
                "size": size,
                "H": H,
                "batch": B,
                "seq": T,
                "measured_ms": round(measured * 1e3, 3),
                "roofline_ms": round(pred * 1e3, 3),
                "bytes_bound_ms": round(bytes_term * 1e3, 3),
                "compute_bound_ms": round(compute_term * 1e3, 3),
                "measured_over_roofline": round(measured / pred, 2),
                "bound": "bytes" if bytes_term > compute_term else "compute",
                "W2_bytes_mb": round(w_bytes / 2**20, 1),
            }
        )
        print(json.dumps(records[-1]), flush=True)
    return records


def main() -> None:
    import jax

    from sheeprl_tpu.utils.profiler import device_peaks

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sizes", nargs="+", default=["M", "L", "XL"], choices=list(DIMS))
    p.add_argument("--batches", nargs="+", type=int, default=[16, 64, 256])
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--chain", type=int, default=8)
    p.add_argument("--repeat", type=int, default=3)
    args = p.parse_args()
    device = jax.devices()[0]
    peaks = device_peaks(device.device_kind)  # raises on a kind without published peaks
    print(json.dumps({"platform": device.platform, "device_kind": device.device_kind, **peaks}), flush=True)
    for size in args.sizes:
        probe_size(
            size, args.batches, args.seq_len, args.chain, args.repeat, peaks["hbm_bytes_per_s"], peaks["bf16_flops"]
        )


if __name__ == "__main__":
    main()
