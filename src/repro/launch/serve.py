"""Serving driver: batched greedy decode with the coded LM head.

``python -m repro.launch.serve --arch qwen3-0.6b --reduced --coded``

Demonstrates the paper's technique live: the unembedding matvec is
MDS-coded over a heterogeneous worker fleet (simulated shifted-exp
runtimes); stragglers that miss the deadline (T* x safety) are erasures
and the logits are recovered from the surviving coded block-products.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core.runtime_model import ClusterSpec
from repro.core.schemes import make_scheme, scheme_names
from repro.data.pipeline import make_extras
from repro.models.model import Model
from repro.runtime.compile_cache import enable_persistent_cache
from repro.runtime.serve_loop import ServeConfig, Server
from repro.serve import make_workload, workload_names
from repro.sim import make_scenario, scenario_names


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--coded", action="store_true",
                    help="serve logits through the coded LM head")
    ap.add_argument("--groups", default="6:2.0,6:0.5",
                    help="heterogeneous fleet as N:mu or N:mu:bandwidth "
                         "groups (bandwidth feeds the comm-delay schemes)")
    ap.add_argument("--bandwidth", type=float, default=None,
                    help="link bandwidth for groups without an explicit "
                         "per-group value (default: infinite = comm-free)")
    ap.add_argument("--scheme", default="optimal", choices=scheme_names(),
                    help="registered allocation scheme for the coded head")
    ap.add_argument("--scheme-n", type=float, default=None,
                    help="code size n for --scheme uniform_n / comm_uniform")
    ap.add_argument("--scheme-r", type=int, default=None,
                    help="completion count r for --scheme uniform_r")
    ap.add_argument("--comm-upload", type=float, default=None,
                    help="fixed per-round transfer cost for --scheme "
                         "comm_aware / comm_uniform (divided by bandwidth)")
    ap.add_argument("--comm-download", type=float, default=None,
                    help="per-row transfer cost for --scheme comm_aware / "
                         "comm_uniform (divided by bandwidth)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route the coded block mix through the Pallas "
                         "coded_matvec kernel")
    ap.add_argument("--scenario", default=None, choices=scenario_names(),
                    help="cluster-dynamics scenario: serve rounds against "
                         "a drifting TRUE fleet (requires --coded)")
    ap.add_argument("--adapt-every", type=int, default=None,
                    help="closed-loop cadence: consume straggler estimates "
                         "and maybe replan the coded head every R serve "
                         "rounds (requires --scenario)")
    ap.add_argument("--adapt-threshold", type=float, default=None,
                    help="hysteresis: replan only when the estimated "
                         "latency improves by this fraction (default 0.05)")
    ap.add_argument("--bucket-quantum", type=int, default=None,
                    help="quantize the coded head's integer loads to this "
                         "multiple and replan via an in-program bucket "
                         "switch: replans within the admitted capacity "
                         "retrace nothing (DESIGN.md §11)")
    ap.add_argument("--rounds", type=int, default=None,
                    help="serve rounds to run under --scenario (default: "
                         "min(scenario horizon, 24))")
    ap.add_argument("--trace", default=None, choices=workload_names(),
                    help="continuous-batching mode: replay this seeded "
                         "request workload through Server.serve instead "
                         "of one fixed-batch generate")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="requests per decode round for --trace workloads "
                         "that accept it (poisson, chat)")
    ap.add_argument("--num-requests", type=int, default=None,
                    help="trace length for --trace (default: the "
                         "workload preset)")
    ap.add_argument("--slots", default="4",
                    help="in-flight stream slots for --trace; 'auto' asks "
                         "the AdaptiveController for a width from measured "
                         "round latency (requires --coded)")
    ap.add_argument("--block-len", type=int, default=None,
                    help="tokens per physical KV block for paged --trace "
                         "serving (default 16)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV block pool size for paged --trace serving "
                         "(default: sized so the trace never exhausts it; "
                         "smaller pools shed on memory pressure)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="admission chunk width for paged --trace serving: "
                         "longer prompts prefill across several admit "
                         "rounds of the same compiled program")
    ap.add_argument("--admission-threshold", type=float, default=1.0,
                    help="admission-control strictness for --trace "
                         "(higher sheds earlier; deadline budgets are "
                         "divided by it)")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="workload trace seed for --trace")
    ap.add_argument("--measure-times", action="store_true",
                    help="measured-reality loop (DESIGN.md §12): time "
                         "each compiled dispatch with a RoundClock and "
                         "adapt from wall-clock observations instead of "
                         "simulated ground truth (requires --coded)")
    ap.add_argument("--telemetry", default=None,
                    help="JSONL telemetry sink (round_timing / "
                         "adapt_decision / request events; feed it to "
                         "repro.launch.obsreport for the ops report)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="serve under jax.profiler into DIR with the serve "
                         "loop's spans as trace annotations: one "
                         ".xplane.pb holds the host spans and the device "
                         "ops on one clock; --trace runs also write "
                         "DIR/scopes.json, each compiled program's "
                         "instruction -> named scope map")
    args = ap.parse_args(argv)
    if args.trace is not None and args.scenario is not None:
        raise SystemExit("--trace and --scenario are separate serving "
                         "modes; pick one")
    if args.scenario is not None and not args.coded:
        raise SystemExit("--scenario requires --coded (a fleet to perturb)")
    if args.adapt_every is not None and args.scenario is None:
        raise SystemExit("--adapt-every requires --scenario (closed-loop "
                         "serving is driven by a scenario trace)")
    if args.measure_times and not args.coded:
        raise SystemExit("--measure-times requires --coded (round times "
                         "are decomposed over the coded fleet)")
    if args.slots == "auto":
        if not args.coded:
            raise SystemExit("--slots auto derives the width from the coded "
                             "fleet's round latency; requires --coded")
    else:
        try:
            args.slots = int(args.slots)
        except ValueError:
            raise SystemExit(f"--slots must be an int or 'auto', "
                             f"got {args.slots!r}")

    # cold-start compile reuse: every program this process builds
    # (bucket branches included) persists to the on-disk JAX cache
    enable_persistent_cache()

    config = get_arch(args.arch)
    if args.reduced:
        config = config.reduced()
    model = Model(config)
    # one compiled program: op-by-op init compiles every op on its own
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))

    cluster = None
    scheme = make_scheme(
        args.scheme, n=args.scheme_n, r=args.scheme_r,
        upload=args.comm_upload, download=args.comm_download,
    )
    if args.coded:
        cluster = ClusterSpec.parse(args.groups, args.bandwidth)
    server = Server(
        model, params, cluster,
        ServeConfig(max_decode_steps=args.max_new, scheme=scheme,
                    use_kernel=args.use_kernel,
                    bucket_quantum=args.bucket_quantum),
    )
    if server.coded_head is not None:
        h = server.coded_head
        print(f"coded LM head [{h.plan.scheme}]: "
              f"kb={h.kb} blocks x {h.block_rows} rows, "
              f"(n,k)=({h.nb},{h.kb}) rate={h.kb/h.nb:.3f}, "
              f"loads/worker={h.plan.loads_per_worker.tolist()}, "
              f"deadline={h.deadline:.4f}")

    with _profiled(args):
        if args.trace is not None:
            _serve_trace(server, args, config)
            return
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
            config.vocab_size,
        ).astype(jnp.int32)
        extras = make_extras(config, args.batch)
        if config.family == "audio":
            extras = {"enc_out": model.encode(params, extras["frames"])}
        if args.scenario is not None:
            _serve_scenario(server, prompts, extras, args, cluster)
            return
        _attach_tracer(server, args)
        t0 = time.perf_counter()
        out = server.generate(prompts, args.max_new, extras=extras)
        dt = time.perf_counter() - t0
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({args.batch * args.max_new / dt:.1f} tok/s)")
        print("sample:", out[0, -args.max_new:].tolist())


def _profiled(args):
    """A ``jax.profiler`` session into ``--profile DIR``, if asked for."""
    if args.profile is None:
        return contextlib.nullcontext()
    return jax.profiler.trace(args.profile)


def _attach_tracer(server, args, telemetry=None):
    """An annotating ``SpanTracer`` on the server (and its coded
    executor) under ``--profile``, so its spans land in the profile;
    mirrors spans to ``telemetry`` when the run has a JSONL sink too."""
    if args.profile is None:
        return None
    from repro.obs.trace import SpanTracer

    tracer = SpanTracer(telemetry, annotate=True)
    server.tracer = tracer
    if server.coded_head is not None:
        server.coded_head.executor.tracer = tracer
    return tracer


def _serve_trace(server, args, config):
    """Continuous-batching mode: replay a seeded workload end to end.

    Requests are admitted into ``--slots`` in-flight stream slots by the
    ``SlotScheduler`` (deadline-class priority, load shedding at
    ``--admission-threshold``); per-request latency is reported in
    virtual rounds (1 decode step = 1 round, 1 batched prefill = 1
    round), throughput in wall-clock tokens/s.
    """
    from repro.runtime.telemetry import Telemetry

    wl = make_workload(
        args.trace, arrival_rate=args.arrival_rate,
        num_requests=args.num_requests, vocab=config.vocab_size,
    )
    trace = wl.trace(seed=args.trace_seed)
    slots = args.slots
    if slots == "auto":
        from repro.runtime.control import AdaptiveController

        # width from measured reality: the controller's coverage-latency
        # view of the fleet (tracker estimates once RoundClock feeds
        # arrive; the planned latency before any) scales a base of 4
        controller = AdaptiveController(server.coded_head.executor)
        slots = controller.recommend_slots(base=4)
        print(f"slots auto -> {slots} "
              f"(coverage latency {controller.coverage_latency():.4f})")
    with Telemetry(args.telemetry) as tel:
        tracer = _attach_tracer(server, args, telemetry=tel)
        clock = None
        if args.measure_times:
            from repro.runtime.timing import RoundClock

            clock = RoundClock(server.coded_head.executor, telemetry=tel)
        rep = server.serve(
            trace, slots=slots,
            admission_threshold=args.admission_threshold,
            telemetry=tel, clock=clock, tracer=tracer,
            block_len=args.block_len, num_blocks=args.num_blocks,
            prefill_chunk=args.prefill_chunk,
        )
    if tracer is not None and rep.scopes is not None:
        os.makedirs(args.profile, exist_ok=True)
        path = os.path.join(args.profile, "scopes.json")
        with open(path, "w") as f:
            json.dump(rep.scopes, f)
        print(f"profile: {args.profile} ({len(tracer.spans)} spans; "
              f"scope maps of {len(rep.scopes)} programs in {path})")
    if clock is not None:
        unit = "-" if clock.unit_s is None else f"{clock.unit_s:.3e}"
        print(f"measured: {clock.fed}/{clock.rounds} rounds fed, "
              f"unit_s={unit}")
    lat = rep.latencies()
    print(f"workload {wl.name!r}: {len(trace)} requests "
          f"(rate={wl.arrival_rate}/round, seed={args.trace_seed})")
    print(f"served {rep.admitted} ({rep.shed} shed), {rep.tokens} tokens "
          f"in {rep.rounds:.0f} rounds "
          f"({rep.prefill_rounds} prefill + {rep.decode_rounds} decode) "
          f"/ {rep.wall_s:.2f}s = {rep.tokens_per_s:.1f} tok/s")
    if len(lat):
        import numpy as np

        print(f"latency rounds: p50={np.percentile(lat, 50):.1f} "
              f"p99={np.percentile(lat, 99):.1f}")


def _serve_scenario(server, prompts, extras, args, cluster):
    """Serve rounds against a drifting TRUE fleet, optionally closed-loop.

    Each round is one ``generate`` call whose straggler masks sample from
    the scenario's current cluster; with ``--adapt-every`` an
    ``AdaptiveController`` observes the round times and replans the
    coded head (rebuilding the compiled pipeline) when its hysteresis
    rule fires — the same controller the trainer runs (DESIGN.md §7).
    With ``--measure-times`` each generate call runs under a
    ``RoundClock`` and the controller ingests MEASURED wall-clock round
    times instead of simulated ground truth (DESIGN.md §12).
    """
    from repro.runtime.control import AdaptConfig, AdaptiveController
    from repro.runtime.telemetry import Telemetry

    # build the scenario AT the round budget so its factory anchors
    # event times/drift rates to the rounds actually served (a default
    # 120-round spec truncated to 24 rounds would never reach its events)
    rounds = args.rounds if args.rounds is not None else 24
    spec = make_scenario(args.scenario, horizon=max(rounds, 1))
    trace = spec.trace(cluster, seed=0)
    head = server.coded_head
    tel = Telemetry(args.telemetry)
    _attach_tracer(server, args, telemetry=tel)
    controller = None
    if args.adapt_every is not None:
        controller = AdaptiveController(
            head.executor,
            AdaptConfig(
                every=args.adapt_every,
                threshold=(0.05 if args.adapt_threshold is None
                           else args.adapt_threshold),
            ),
            telemetry=tel,
            on_replan=server.refresh_coded_head,
        )
    clock = None
    if args.measure_times:
        from repro.runtime.timing import RoundClock

        clock = RoundClock(head.executor, telemetry=tel)
    key = jax.random.PRNGKey(7)
    t0 = time.perf_counter()
    toks = 0
    for t in range(rounds):
        true_cluster = trace.at(t)
        server.set_true_cluster(true_cluster)
        gkey = jax.random.fold_in(key, t)
        # the observation key matches the simulated path round for round,
        # so measured and simulated runs are comparable draw by draw
        okey = jax.random.fold_in(key, 10_000 + t)
        d = None
        if clock is not None:
            timing = clock.measure(
                lambda: server.generate(
                    prompts, args.max_new, key=gkey, extras=extras
                ),
                key=okey, true_cluster=true_cluster,
            )
            out = timing.result
            if controller is not None:
                d = controller.observe_timing(timing)
        else:
            out = server.generate(
                prompts, args.max_new, key=gkey, extras=extras
            )
            if controller is not None:
                d = controller.observe_truth(okey, true_cluster)
        toks += out.shape[0] * args.max_new
        if d is not None and d.replanned:
            if clock is not None and head.executor.last_replan_structural:
                clock.discard_next()  # next round pays the retrace
            print(f"[round {t}] replanned ({d.reason}): "
                  f"deadline -> {head.deadline:.4f}, "
                  f"loads {head.plan.loads_per_worker.tolist()}")
    dt = time.perf_counter() - t0
    print(f"scenario {spec.name!r}: {rounds} rounds, {toks} tokens in "
          f"{dt:.2f}s ({toks / dt:.1f} tok/s)")
    if clock is not None:
        unit = "-" if clock.unit_s is None else f"{clock.unit_s:.3e}"
        print(f"measured: {clock.fed}/{clock.rounds} rounds fed, "
              f"unit_s={unit}")
    if controller is not None:
        replans = [d for d in controller.decisions if d.replanned]
        print(f"controller: {len(controller.decisions)} decisions, "
              f"{len(replans)} replans at rounds "
              f"{[d.round for d in replans]}")
    tel.close()


if __name__ == "__main__":
    main()
