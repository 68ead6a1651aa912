"""Readings for the limits of the hybrid token policy's cell, by hand on the chip::

    python3 perfbench/calibrate_token_ppo_lfm2.py --workload lfm2_24b_a2b_ep8.train --seed <n> [--seconds 8]

One run of the cell through the harness, then every number ``correct`` compares
(``algorithms/token_ppo_lfm2.py``), for the program and for what must not pass,
each put in the program's place against the float32 reference: the reference
with its weights in bfloat16, and rounded to 4 exponent and 3 mantissa bits,
and three planted faults (one held expert left out; half of the minibatch left
out; the convolution state of the episode before kept through a reset). One
JSON line a side, with the numbers that side can move: ``half_batch`` no
player's, ``conv_state_kept`` the player's alone, ``float8`` its player's and
its first step's losses (its gradient would take two minutes more and every
number it has fails already). Each side's numbers then go through the limits
the cell's file has, as the program's do (``correct.judge`` over the numbers
that side can move): the line says whether the side came out ``correct`` and
by which limits it fails, and the run's own ``correct`` is false if the program
fails or any control or fault passes. It reads what ``token_ppo_lfm2.verify``
compares; ``calibrate_token_ppo.py`` is the latent-attention model's tool.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

#: name -> (the weights' precision, held experts left out, half the minibatch left out, the convolution state kept, which parts are read)
SIDES = {"half_batch": ("float32", (), True, False, ("train",)),
         "expert_left_out": ("float32", (1,), False, False, ("train", "player")),
         "bfloat16_weights": ("bfloat16_weights", (), False, False, ("train", "player")),
         "conv_state_kept": ("float32", (), False, True, ("player",)),
         "float8": ("float8", (), False, False, ("losses", "player"))}  # fmt: skip


def judged(side: str, numbers, limits):
    """The side's line: its numbers, and what the cell's limits make of them."""
    from perfbench.correct import judge

    fails = sorted(k for k, v in judge(numbers, {k: limits[k] for k in numbers if k in limits}).items() if not v["ok"])
    return {"side": side, "correct": not fails, "fails_by": fails, **numbers}


def readings(cfg, seed, capture, limits, stamps):
    import jax

    from perfbench.algorithms import token_ppo_lfm2 as algorithm
    from perfbench.calibrate_token_ppo import _release
    from perfbench.references import token_ppo_lfm2 as reference

    ok, compared, not_compared = algorithm.verify(cfg, seed, capture, limits, stamps)
    print(json.dumps({"side": "program", "correct": ok, **{k: v["value"] for k, v in compared.items()}, **not_compared}), flush=True)
    # a state returned unchanged reads 1 in ``change`` by construction
    lines = [judged("state_unchanged", {"change": 1.0}, limits)]
    print(json.dumps(lines[0]), flush=True)
    weights = jax.device_put(capture.seeded)
    m, a = cfg["model"], cfg["algo"]
    ref_train = algorithm.train_side(cfg, weights, capture.steps)
    _release()
    for name, (precision, without, half, kept, parts) in SIDES.items():
        theirs = reference.cast(weights, precision)
        numbers = {}
        if "train" in parts:
            numbers.update(algorithm.train_gaps(algorithm.train_side(cfg, theirs, capture.steps, without, half), ref_train, capture.seeded))
        if "losses" in parts:
            got = reference.losses_only(theirs, m, a, algorithm.aligned_sequences(capture.steps[0]["batch"]), without)
            want = ref_train["losses"][0]
            numbers.update({k: abs(got[k] - want[k]) / max(abs(want["policy_scale" if k == "policy_loss" else k]), 1e-12) for k in got if k != "policy_scale"})
        if "player" in parts:
            _, arrays = algorithm.player_gaps(m, theirs, capture.player, without=without, conv_state_kept=kept)
            numbers.update(algorithm.player_gaps(m, weights, capture.player, against=arrays)[0])
            del arrays
        lines.append(judged(name, numbers, limits))
        print(json.dumps(lines[-1]), flush=True)
        del theirs
        _release()
    passing = [line["side"] for line in lines if line["correct"]]
    print(json.dumps({"side": "verdict", "program_correct": ok, "controls_and_faults_that_pass": passing}), flush=True)
    return ok and not passing, compared, not_compared


def main() -> None:
    from perfbench import run

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=8.0)  # the window holds whole cycles: one of 5.6 to 5.8 s at least
    args = parser.parse_args()
    print(json.dumps(run.run_cell(args.workload, args.seed, args.seconds, False, verify=readings)), flush=True)


if __name__ == "__main__":
    main()
