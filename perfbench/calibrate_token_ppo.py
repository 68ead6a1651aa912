"""Readings for the limits of the token policy's cell, by hand on the chip::

    python3 perfbench/calibrate_token_ppo.py --workload glm47_flash_ep8.train --seed <n> [--seconds 8] [--losses-only]

One run of the cell through the harness, then every number ``correct`` compares
(``algorithms/token_ppo.py``), for the program and for what must not pass, each
put in the program's place against the float32 reference: the reference with
its weights in bfloat16, and rounded to 4 exponent and 3 mantissa bits, and two
planted faults (one held expert left out; half of the minibatch left out). One
JSON line a side; ``--losses-only`` reads of the four only their first step's
losses (forward passes: minutes less). It reads what ``token_ppo.verify`` compares; Dreamer-V3's tool
is ``calibrate.py``.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

#: name -> (the weights' precision, held experts left out, half the minibatch left out)
SIDES = {"half_batch": ("float32", (), True), "expert_left_out": ("float32", (1,), False),
         "bfloat16_weights": ("bfloat16_weights", (), False), "float8": ("float8", (), False)}  # fmt: skip


def _release() -> None:
    """Freed trees of gigabytes go back to the system, not to the allocator's free lists."""
    import ctypes
    import gc

    gc.collect()
    with contextlib.suppress(OSError, AttributeError):
        ctypes.CDLL("libc.so.6").malloc_trim(0)


def readings(cfg, seed, capture, limits, stamps, losses_only=False):
    import jax

    from perfbench.algorithms import token_ppo as algorithm
    from perfbench.references import token_ppo as reference

    ok, compared, not_compared = algorithm.verify(cfg, seed, capture, limits, stamps)
    print(json.dumps({"side": "program", **{k: v["value"] for k, v in compared.items()}, **not_compared}), flush=True)
    weights = jax.device_put(capture.seeded)
    if losses_only:
        # the first step's losses alone (forward passes: minutes less): each side's gap to the float32 reference's
        m, a = cfg["model"], cfg["algo"]
        sequences = algorithm.aligned_sequences(capture.steps[0]["batch"])
        ref = reference.losses_only(weights, m, a, sequences)
        for name, (precision, without, half) in SIDES.items():
            theirs = reference.losses_only(reference.cast(weights, precision), m, a, sequences[: len(sequences) // 2] if half else sequences, without)
            gaps = {k: abs(theirs[k] - ref[k]) / max(abs(ref["policy_scale" if k == "policy_loss" else k]), 1e-12) for k in theirs if k != "policy_scale"}
            print(json.dumps({"side": name, **gaps}), flush=True)
            _release()
        return ok, compared, not_compared
    ref_train = algorithm.train_side(cfg, weights, capture.steps)
    _release()
    for name, (precision, without, half) in SIDES.items():
        theirs = reference.cast(weights, precision)
        numbers = algorithm.train_gaps(algorithm.train_side(cfg, theirs, capture.steps, without, half), ref_train, capture.seeded)
        _, arrays = algorithm.player_gaps(cfg["model"], theirs, capture.player, without=without)
        numbers.update(algorithm.player_gaps(cfg["model"], weights, capture.player, against=arrays)[0])
        print(json.dumps({"side": name, **numbers}), flush=True)
        del theirs, arrays
        _release()
    return ok, compared, not_compared


def main() -> None:
    from perfbench import run

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=8.0)  # the window holds whole cycles: one of 5.6 to 5.8 s at least
    parser.add_argument("--losses-only", action="store_true", help="the controls' first-step losses alone, not their gradients nor their player")
    args = parser.parse_args()
    verify = functools.partial(readings, losses_only=args.losses_only)
    print(json.dumps(run.run_cell(args.workload, args.seed, args.seconds, False, verify=verify)), flush=True)


if __name__ == "__main__":
    main()
