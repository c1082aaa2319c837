"""Benchmark of coded-head serving on one chip, one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); its limits are in
``bench/checks/<cell>.json``. The run:

1. set-up: draws the weights on the device from ``--seed``, builds the
   program's ``Server`` with its MDS-coded LM head, and serves one replay
   of requests that no window replay uses, to warm every program the
   window will run;
2. window: one ``Server.serve`` call per replay, back to back, each on
   requests drawn from ``(--seed, replay)`` with its own key, until
   ``--seconds`` have passed; the window ends with the last replay. Every
   replay has the same sizes in the same order (``bench/traffic.py``), so
   the window does the same work for every seed;
3. check: rebuilds what the timed path served, frees the program, runs a
   sample of it through the plain reference (``bench/correct.py``);
4. prints the checks on standard error and one JSON line on standard
   output: end-to-end metrics, or with ``--trace 1`` the per-layer metrics
   (``bench/metrics/<metric>.py``), read from the second replay: its host
   spans, and a profile of its first ``TRACE_SECONDS`` of rounds.

It runs only on a TPU with as many chips as the cell asks for, and exits
non-zero with no result line otherwise.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
#: the persistent compile cache: one fixed directory inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: the serve loop's compiled program, as the device trace names its runs
PROGRAM = "_serve_step_paged_program"
#: the traced slice: whole rounds over the first seconds of the second replay
TRACE_SECONDS = 4.0
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    loads included), and the number of backend compiles, from its own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_count(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            kind = event.rsplit("_", 1)[-1]
            if kind in self.cache:
                self.cache[kind] += 1

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1


def load_cell(name: str, root: str = ROOT) -> tuple[dict, dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix, limits) of cell ``name``."""
    import traffic

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "checks", f"{name}.json")) as f:
        limits = json.load(f)
    return bench, cell, cfg, traffic.load(cell["traffic"]), limits


def program_config(cfg: dict, base=None):
    """The program's ``ModelConfig`` for ``cfg``: its registry entry, in
    the configuration's dtype, checked against every size the file
    states, so the file is what runs. The reference the file names says
    what to compare (``Dims.program_sizes``, ``Dims.constants_off``)."""
    import correct

    if base is None:
        from repro.configs import get_arch

        base = get_arch(cfg["system"]["arch"])
    mc = dataclasses.replace(base, param_dtype=cfg["torch_dtype"],
                             compute_dtype="bfloat16")
    d = correct.reference(cfg).Dims.of(cfg)
    off = {k: (getattr(mc, k), v) for k, v in d.program_sizes().items()
           if getattr(mc, k) != v}
    off.update(d.constants_off())
    if off:
        raise SystemExit(f"program config differs from the file: {off}")
    return mc


def build(cfg: dict, mix: dict, seed: int, model_config):
    """Weights from the seed, the ``Server`` and its recorder."""
    import jax

    import correct
    import weights
    from record import Recorder
    from repro.core.runtime_model import ClusterSpec
    from repro.models.model import Model
    from repro.runtime.serve_loop import ServeConfig, Server

    model = Model(model_config)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = jax.block_until_ready(weights.program_params(
        shapes, seed, correct.reference(cfg).STACKED))
    sysc = cfg["system"]
    server = Server(model, params, ClusterSpec.parse(sysc["fleet"]), ServeConfig(
        block_rows=sysc["block_rows"], deadline_safety=sysc["deadline_safety"],
        scheme=sysc["scheme"], paged=True, block_len=mix["block_len"],
        num_blocks=mix["num_blocks"], prefill_chunk=mix["prefill_chunk"],
    ))
    rec = Recorder(server._serve_step_paged_fn)
    server._serve_step_paged_fn = rec
    return server, rec


def serve(server, requests, mix, key, tracer=None):
    return server.serve(
        requests, slots=mix["slots"], decode_block=mix["decode_block"],
        queue_cap=mix["queue_cap"], key=key, block_len=mix["block_len"],
        num_blocks=mix["num_blocks"], prefill_chunk=mix["prefill_chunk"],
        tracer=tracer,
    )


def replay_trace(mix: dict, seed: int, i: int, vocab: int) -> list:
    """Requests of window replay ``i``; the warm-up replay is ``i = -1``."""
    import traffic
    from repro.serve.workload import Request

    return traffic.make_trace(mix, [seed, i + 1], vocab, Request)


def replay_key(seed: int, i: int):
    """Key of window replay ``i``; the warm-up replay is ``i = -1``."""
    import jax
    import numpy as np

    import weights

    k = jax.random.fold_in(weights.seed_key(seed), np.uint32(0x5E12E))
    return jax.random.fold_in(k, np.uint32(i + 1))


def _load_metric(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class TracedRun:
    """What the per-layer readers read (``bench/metrics/*.py``)."""

    dims: object  # the reference's ``Dims`` of the configuration
    peaks: dict
    compile_s: float
    slots: int
    program: str
    reports: list
    spans: list
    dispatches: list
    trace: object


def run(args, bench: dict, cell: dict, cfg: dict, mix: dict, limits: dict, *,
        model_config=None, control: bool = False, log=print) -> dict:
    """Set-up, window and check of one cell; returns the result line.

    Everything after the look for a chip, so that a test can drive it on
    the CPU at a small size. With ``control``, the line also carries the
    float8 control's widest gap on the same sample (``control_gap``).
    """
    import jax
    import numpy as np

    import correct
    import peaks as peaks_mod
    import traffic
    import xplane
    from record import replay_requests
    from repro.runtime.compile_cache import enable_persistent_cache
    from spans import Tracer

    devices = jax.devices()
    log(f"compile cache: {enable_persistent_cache()}")
    clock = CompileClock()
    mc = program_config(cfg, model_config)
    dims = correct.reference(cfg).Dims.of(cfg)
    t = time.perf_counter()
    server, rec = build(cfg, mix, args.seed, mc)
    log(f"set-up: weights + server {time.perf_counter() - t!r} s; coded head "
        f"(nb, kb)=({server.coded_head.nb}, {server.coded_head.kb})")
    t = time.perf_counter()
    serve(server, replay_trace(mix, args.seed, -1, dims.vocab), mix,
          replay_key(args.seed, -1))
    rec.take()
    log(f"set-up: warm-up replay {time.perf_counter() - t!r} s, chunk sizes "
        f"{sorted(rec.steps_seen)}")
    compile_s = clock.seconds
    traces0, compiles0 = server.serve_traces, clock.backend_compiles
    clock0, cache0 = clock.seconds, dict(clock.cache)
    setup_s = time.perf_counter() - T0
    log(f"set-up: {setup_s!r} s, of which compile clock {compile_s!r} s; "
        f"persistent cache {clock.cache}")

    reports, offered, tracer, traced, trace_dir = [], [], None, None, None
    # no collection pauses in the window: the heap is frozen as set-up
    # left it, and the window allocates little
    gc.collect()
    gc.freeze()
    gc.disable()
    t_start = time.perf_counter()
    while True:
        i = len(reports)
        requests = replay_trace(mix, args.seed, i, dims.vocab)
        profiled = bool(args.trace) and i == 1
        if profiled:
            first, cut, ann = len(rec.calls), [], []

            def stop_profile():
                # the slice ends once the device has run what it was given
                if rec.calls:
                    jax.block_until_ready(rec.calls[-1].toks)
                ann[0].__exit__(None, None, None)
                jax.profiler.stop_trace()
                cut.append(len(rec.calls))

            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            # the traced slice, as the host span ``replay`` (an annotation
            # made before the profile starts is never recorded)
            ann.append(jax.profiler.TraceAnnotation("replay"))
            ann[0].__enter__()
            tracer = Tracer(TRACE_SECONDS, stop_profile)
        rep = serve(server, requests, mix, replay_key(args.seed, i),
                    tracer if profiled else None)
        if profiled:
            tracer.finish()
            traced = (rep, first, cut[0], len(rec.calls))
        reports.append(rep)
        offered.extend(requests)
        if time.perf_counter() - t_start >= args.seconds and (
                not args.trace or len(reports) >= 2):
            break
    wall = time.perf_counter() - t_start
    gc.enable()
    gc.unfreeze()
    log(f"window: {len(reports)} replays in {wall!r} s; serve_traces "
        f"{traces0} -> {server.serve_traces}; backend compiles in window "
        f"{clock.backend_compiles - compiles0}, compile clock "
        f"{clock.seconds - clock0!r} s, cache hits "
        f"{clock.cache['hits'] - cache0['hits']}; replay seconds "
        f"{[round(r.wall_s, 4) for r in reports]}")
    stats = devices[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    calls = rec.take()
    server = rec = None
    gc.collect()
    served, _ = replay_requests(calls)
    want = {}
    for r in offered:
        want.setdefault(r.prompt, set()).add(r.out_len)
    unmatched = sum(1 for s in served
                    if len(s.tokens) not in want.get(tuple(s.prompt), ()))
    attempted = len(offered)
    done = sum(sum(f.outcome == "done" for f in r.finished) for r in reports)
    t = time.perf_counter()
    picked = correct.sample(served, args.seed)
    length = correct.row_length(traffic.max_context(mix))
    gap = (float(np.max(correct.gaps(cfg, args.seed, picked, length=length)))
           if picked else float("inf"))
    log(f"check: reference over {len(picked)} requests, "
        f"{sum(len(s.tokens) for s in picked)} served tokens, "
        f"{time.perf_counter() - t!r} s")
    checks = {
        "logit_gap": {"value": gap, "limit": limits["logit_gap"]},
        "unmatched_requests": {"value": unmatched, "limit": 0},
        "missing_requests": {"value": attempted - len(served), "limit": 0},
    }
    ok = all(c["limit"] is not None and c["value"] <= c["limit"]
             for c in checks.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if not args.trace:
        rounds = sum(r.decode_rounds + r.prefill_rounds for r in reports)
        tokens = sum(r.tokens for r in reports)
        values = {"tokens_per_s": tokens / wall, "round_ms": 1e3 * wall / rounds,
                  "peak_hbm_gb": peak / 1e9 if peak else None, "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        rep, lo, cut, hi = traced
        _, dispatches = replay_requests(calls[lo:hi])
        dispatches = dispatches[: cut - lo]
        t = time.perf_counter()
        tr = xplane.load(xplane.find(trace_dir))
        log(f"trace: {sum(map(len, tr.ops))} device ops read in "
            f"{time.perf_counter() - t!r} s")
        if args.keep_trace:
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = TracedRun(dims=dims, peaks=peaks_mod.peaks(dev.device_kind),
                        compile_s=compile_s, slots=mix["slots"], program=PROGRAM,
                        reports=[rep], spans=tracer.spans,
                        dispatches=dispatches, trace=tr)
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            v = _load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo_ns, hi_ns = xplane.window(tr)
        device["busy_s"] = xplane.busy_ns(tr) * 1e-9
        device["window_s"] = (hi_ns - lo_ns) * 1e-9
        breakdown = {"device_ops": xplane.top_ops(tr), "idle_gaps": xplane.idle_gaps(tr)}
        log(f"traced slice: {len(dispatches)} of {hi - lo} dispatches, "
            f"{len(xplane.program_runs(tr, PROGRAM))} program runs in the trace, "
            f"weights {dims.weight_bytes()} B")
    out = {"correct": ok, "attempted": attempted, "failed": attempted - done,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["control_gap"] = float(np.max(correct.gaps(
            cfg, args.seed, picked, length=length, control=True)))
    out["checks"] = checks
    return out


def use_cache_dir() -> None:
    """JAX's persistent compile cache in ``CACHE_DIR``, never evicted."""
    # the cache directory is git-ignored, so a fresh checkout lacks it
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    # no eviction: an environment that sets a size limit turns on JAX's
    # access-time files, and one entry without its file fails every write
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profile of a --trace 1 run to this directory")
    args = ap.parse_args(argv)
    bench, cell, cfg, mix, limits = load_cell(args.workload)
    use_cache_dir()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    out = run(args, bench, cell, cfg, mix, limits, log=log)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
