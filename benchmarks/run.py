"""Run every paper-figure benchmark + the roofline report.

``PYTHONPATH=src python -m benchmarks.run [--only fig4,fig9] [--skip roofline]``
(``--list`` prints the registered benchmark names and exits.)
"""
from __future__ import annotations

import argparse
import time

from benchmarks import (
    alloc_fastpath,
    fig2,
    fig3,
    fig4,
    fig5,
    fig6,
    fig7,
    fig8,
    fig9,
    fig_adapt,
    fig_comm,
    fig_grad,
    roofline,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset to run, e.g. fig4,fig9")
    ap.add_argument("--skip", default=None,
                    help="comma-separated subset to leave out, e.g. "
                         "fig_adapt,roofline")
    ap.add_argument("--list", action="store_true",
                    help="print registered benchmark names and exit")
    args = ap.parse_args()
    mods = {
        "fig2": fig2, "fig3": fig3, "fig4": fig4, "fig5": fig5,
        "fig6": fig6, "fig7": fig7, "fig8": fig8, "fig9": fig9,
        "fig_comm": fig_comm, "fig_grad": fig_grad, "fig_adapt": fig_adapt,
        "alloc_fastpath": alloc_fastpath, "roofline": roofline,
    }
    if args.list:
        print("\n".join(mods))
        return
    names = args.only.split(",") if args.only else list(mods)
    skips = args.skip.split(",") if args.skip else []
    unknown = [n for n in names + skips if n not in mods]
    if unknown:
        raise SystemExit(
            f"unknown benchmark(s): {', '.join(unknown)}; "
            f"available: {', '.join(mods)}"
        )
    names = [n for n in names if n not in set(skips)]
    if not names:
        raise SystemExit("nothing to run: --skip removed every benchmark")
    for name in names:
        print(f"\n{'=' * 72}\n{name}\n{'=' * 72}")
        t0 = time.perf_counter()
        mods[name].run()
        print(f"[{name} done in {time.perf_counter() - t0:.1f}s]")


if __name__ == "__main__":
    main()
