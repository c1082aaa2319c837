"""Readings to set a cell's limits from, on the chip, in one process.

    python bench/calibrate.py --workload <cell> --seed <first> --seeds <n> \
        --control <m> --out <file.jsonl>

For each of ``n`` seeds (``first``, ``first + 7919``, ...) it makes a run
of the harness whose window is one replay at the cell's own load: set-up
from the seed, the replay, and the check on the same sample a run
compares. It writes one JSON line per seed with the widest served-token
gap (the lower reading of ``logit_gap``) and, for the first ``m`` seeds,
the float8 control's widest gap on the same sample (the upper reading).
``bench/checks/<cell>.json`` holds the limit set between the two.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=4)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench, cell, cfg, mix, limits = R.load_cell(args.workload)
    R.use_cache_dir()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    with open(args.out, "a") as f:
        for k in range(args.seeds):
            seed = args.seed + 7919 * k
            t = time.perf_counter()
            run_args = types.SimpleNamespace(seed=seed, seconds=0.0, trace=0,
                                             keep_trace=None)
            out = R.run(run_args, bench, cell, cfg, mix, limits,
                        control=k < args.control, log=log)
            line = {"seed": seed, "control": out.get("control_gap"),
                    **{name: c["value"] for name, c in out["checks"].items()},
                    "attempted": out["attempted"], "failed": out["failed"],
                    "s": time.perf_counter() - t}
            f.write(json.dumps(line) + "\n")
            f.flush()
            log(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
