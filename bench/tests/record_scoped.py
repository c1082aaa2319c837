"""Record the tiny cell's traced slice and the programs it ran, for
``test_scoped_recorded.py``.

    python bench/tests/record_scoped.py OUT_DIR [--seed N]

Runs the harness on the tiny configuration (``tiny.py``) with
``--trace 1`` on the chip, and writes, xz-compressed, into OUT_DIR:

* ``tiny_scoped.xplane.pb.xz``: the profile of the traced slice;
* ``tiny_scoped.json.xz``: the optimized HLO text of the compiled serve
  program for each chunk size the traced replay ran, the traced slice's
  dispatches (prompt chunk spliced or not, decode steps) in order, and
  the per-layer readings of the run.

It needs a TPU: the CPU's profile has no chip plane.
"""
from __future__ import annotations

import argparse
import glob
import json
import lzma
import os
import sys
import tempfile
import types

import tiny  # noqa: F401  (puts bench/ and src/ on the path)

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    args = ap.parse_args(argv)

    import jax

    from repro.runtime.serve_loop import Server

    if jax.devices()[0].platform != "tpu":
        print("record_scoped: needs a TPU", file=sys.stderr)
        return 2
    texts, ctx = {}, {}
    program_scopes = Server._program_scopes

    def keep_text(self, probe, sizes):
        for steps in sizes:
            texts[steps] = self._serve_step_paged_jit.lower(
                *probe, steps=steps).compile().as_text()
        return program_scopes(self, probe, sizes)

    load_metric = R._load_metric

    def load(name):
        read = load_metric(name).read

        def keep_run(run):
            ctx["run"] = run
            return read(run)
        return types.SimpleNamespace(read=keep_run)

    Server._program_scopes = keep_text
    R._load_metric = load
    with open(os.path.join(os.path.dirname(tiny.BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {"name": "qwen3-0.6b.lmsys-chat", "chips": 1}
    with tempfile.TemporaryDirectory() as keep:
        run_args = types.SimpleNamespace(seed=args.seed, seconds=0.5, trace=1,
                                         keep_trace=keep)
        out = R.run(run_args, bench, cell, tiny.CFG, tiny.MIX,
                    {"logit_gap": tiny.LIMIT}, model_config=tiny.model_config(),
                    log=lambda m: print(m, file=sys.stderr, flush=True))
        (path,) = glob.glob(os.path.join(keep, "**", "*.xplane.pb"), recursive=True)
        os.makedirs(args.out, exist_ok=True)
        with open(path, "rb") as f, lzma.open(
                os.path.join(args.out, "tiny_scoped.xplane.pb.xz"), "wb") as g:
            g.write(f.read())
    run = ctx["run"]
    record = {
        "hlo": {str(k): v for k, v in sorted(texts.items())},
        "dispatches": [[d.prefill, d.steps] for d in run.dispatches],
        "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        "device": out["device"],
    }
    with lzma.open(os.path.join(args.out, "tiny_scoped.json.xz"), "wt") as g:
        json.dump(record, g)
    print(json.dumps({"correct": out["correct"], "metrics": record["metrics"],
                      "dispatches": len(record["dispatches"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
