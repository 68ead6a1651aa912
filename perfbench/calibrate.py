"""Readings for the limits of ``correct``: ``python3 perfbench/calibrate.py
--workload <cell> --seeds 1,2,3 [--controls 3] [--faults half_batch]``.

Not part of a benchmark run. In one process it drives the program through a
short window on each seed and prints, per seed, every number read against the
float32 reference and its verdict by the cell's limits; for the first
``--controls`` seeds it also puts the reference in the program's place
computed in each lower precision (the controls: ``bfloat16_weights`` below
float32, ``float8`` below bfloat16) and, if asked, with half of the batch
left out (a fault), and prints what those read and which limits they fail.
``--player-controls`` does so for the player's forwards alone, which costs
no gradient step. The limits in ``workloads/<cell>.json`` are set from these
lines by hand, as PERF.md records.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SIDE = ("losses", "first_grads", "first_grad_samples", "change", "player")


def _raw(side, ref):
    """Everything a number could be made of, leaf by leaf and step by step:
    written with ``--out`` so that a candidate number is tried without a run."""
    import numpy as np

    leaves = {}
    for tree, want in ref["first_grads"].items():
        for leaf in want:
            a = np.asarray(side["first_grad_samples"][tree][leaf], np.float64)
            b = np.asarray(ref["first_grad_samples"][tree][leaf], np.float64)
            cos = float(a @ b) / max(float(np.linalg.norm(a) * np.linalg.norm(b)), 1e-300)
            leaves[f"{tree}:{leaf}"] = [side["first_grads"][tree][leaf], want[leaf], side["change"][tree][leaf],
                                        ref["change"][tree][leaf], 1.0 - cos, int(a.size)]  # fmt: skip
    return {"losses": side["losses"], "ref_losses": ref["losses"], "leaves": leaves}


def _failed(numbers, limits):
    from perfbench import correct

    return sorted(k for k, v in correct.judge(numbers, {k: limits[k] for k in limits if k in numbers}).items() if not v["ok"])


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=0)
    parser.add_argument("--player-controls", type=int, default=0)
    parser.add_argument("--control-policies", default="bfloat16_weights,float8")
    parser.add_argument("--faults", default="")
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    import jax

    from perfbench import correct, loader, run
    from perfbench.references import dreamer_v3 as reference

    limits = loader.Cell(args.workload).workload["limits"]
    rows, kept = [], {}

    def keep_capture(cfg, seed, capture, limits, stamps):
        kept.update(capture=capture, seed=seed, stamps=stamps)
        return True, {}, {}

    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        result = run.run_cell(args.workload, seed, args.seconds, False, warm_steps=2, verify=keep_capture)
        capture, cfg = kept["capture"], kept["capture"].cfg
        row = {"seed": seed, "metrics": result["metrics"], "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        capture.replay_player()
        ref = correct.follow(cfg, capture, "float32")
        program = correct.program_side(capture, ref)
        detail = {}
        numbers = correct.compare(program, ref, detail)
        numbers["ring_rows"] = float(correct.ring_rows(cfg, kept["seed"], capture.batches, kept["stamps"])["bad"])
        row["program"], row["program_fails"] = numbers, _failed(numbers, limits)
        row["program_raw"] = _raw(program, ref)
        row["program_trees"] = {t: {k: (v["gap"], v["leaf"]) for k, v in d.items()} for t, d in detail.items()}
        if n < args.controls:
            planted = [(p, p) for p in args.control_policies.split(",") if p] + [(f, None) for f in args.faults.split(",") if f]
            for fault, policy in planted:
                other = correct.follow(cfg, capture, policy or "float32", fault=None if policy else fault)
                side = {k: other[k] for k in SIDE}
                row[fault] = correct.compare(side, ref)
                row[f"{fault}_fails"] = _failed(row[fault], limits)
                row[f"{fault}_raw"] = _raw(side, ref)
        elif n < args.player_controls:
            start = jax.device_put(capture.seeded)
            for policy in (p for p in args.control_policies.split(",") if p):
                other = correct.player_side(reference.Model(cfg, policy), start[0], start[1], capture.player)
                row[f"{policy}_player"] = correct.player_gaps(other, ref["player"])
                row[f"{policy}_player_fails"] = _failed(row[f"{policy}_player"], limits)
        print("[calibrate] " + json.dumps({k: v for k, v in row.items() if not k.endswith("_raw")}), flush=True)
        rows.append(row)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
