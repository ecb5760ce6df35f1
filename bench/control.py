#!/usr/bin/env python3
"""Reads the control of a cell on the chip: the configuration's reference
computed one precision lower (bfloat16) in the program's place, over the
same queries a run answers, against the float32 reference.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --batches 44

One line per seed with each compared number. The smallest over the seeds
is the upper reading a limit in the configuration must stay under. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import generator, run, work  # noqa: E402


def readings(cfg: dict, traffic: dict, seed: int, batches: int) -> dict:
    made = generator.make_traffic(cfg, traffic, seed, batches, cfg["batch"])
    ref = work.reference(cfg)
    sel = np.arange(len(made))
    want_pooled = "pooled_gap" in cfg["correct"]
    ref_l, ref_p = ref.reference(seed, cfg, made.indices, made.dense, sel,
                                  want_pooled=want_pooled)
    ctl_l, ctl_p = ref.reference(seed, cfg, made.indices, made.dense, sel,
                                  dtype=jnp.bfloat16,
                                  want_pooled=want_pooled)
    out = {"logit_gap": run.gap(ctl_l, ref_l)}
    if want_pooled:
        out["pooled_gap"] = run.gap(ctl_p, ref_p)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--batches", type=int, required=True,
                    help="full batches of queries compared per seed")
    args = ap.parse_args()
    spec = run.Spec(args.workload)
    run.require_chips(spec.cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(spec.config, spec.traffic, seed, args.batches)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": r}), flush=True)


if __name__ == "__main__":
    main()
