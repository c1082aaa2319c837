"""Smoke run of the main serving path on a TPU, at published widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: distributed coded matvec

One chip: serves a seeded chat trace with qwen3-0.6b at its published
widths (random weights from a seed) through ``Server.serve`` on the paged
KV pool, with every token passing the MDS-coded LM head on the
``6:2.0,6:0.5`` fleet — the path ``python -m repro.launch.serve --arch
qwen3-0.6b --coded --trace chat`` runs. It then compares the coded head's
decoded logits with the plain logits on the same prompts, with every
worker finishing and with a fixed erasure pattern inside the plan's
tolerance, and runs the two Pallas kernels of the serve path against
their jax routes.

``--four-chips`` runs only the paper's distributed coded matvec: a
``DecodePipeline`` over a 4-device ``workers`` mesh with the 12-worker
fleet (3 workers per chip) at the coded head's real shape, compared with
the same decode on one chip and with the plain ``A @ x``.

Every check that fails raises, so the script exits non-zero. It also
exits non-zero, printing no result line, when JAX finds no TPU. The last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.configs import get_arch  # noqa: E402
from repro.core.coded_matvec import DecodePipeline, pack_coded_matrix  # noqa: E402
from repro.core.runtime_model import ClusterSpec  # noqa: E402
from repro.kernels import paged_attention as pa  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.runtime.compile_cache import enable_persistent_cache  # noqa: E402
from repro.runtime.executor import CodedRoundExecutor  # noqa: E402
from repro.runtime.serve_loop import ServeConfig, Server  # noqa: E402
from repro.serve import make_workload  # noqa: E402

ARCH = "qwen3-0.6b"
GROUPS = "6:2.0,6:0.5"
#: workers that miss the deadline in the erasure case: both fast workers
#: holding the first systematic blocks plus one slow worker — 230 of the
#: 264 spare blocks of the full-width plan
ERASED_WORKERS = (0, 1, 6)
#: decoded logits must match the plain ones within REL_TOL * max|logit|
REL_TOL = 1e-3

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (persistent-cache loads count as compile)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration


def compare_logits(coded, plain, vocab: int) -> dict:
    """Max abs error of decoded vs plain logits over the real vocab, and
    greedy agreement wherever the plain top-2 gap exceeds the bound."""
    coded = np.asarray(coded, np.float64)[:, :vocab]
    plain = np.asarray(plain, np.float64)[:, :vocab]
    err = float(np.max(np.abs(coded - plain)))
    bound = REL_TOL * float(np.max(np.abs(plain)))
    top2 = np.sort(plain, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > bound
    agree = np.argmax(coded, -1) == np.argmax(plain, -1)
    return {
        "max_abs_err": err,
        "bound": bound,
        "greedy_checked": int(decided.sum()),
        "greedy_agree": int((agree & decided).sum()),
        "ok": err <= bound and bool(np.all(agree[decided])),
    }


def _pad_prompts(trace):
    cap = max(r.prompt_len for r in trace)
    tokens = np.zeros((len(trace), cap), np.int32)
    lengths = np.zeros((len(trace),), np.int32)
    for i, r in enumerate(trace):
        tokens[i, : r.prompt_len] = r.prompt
        lengths[i] = r.prompt_len
    return tokens, lengths


def erasure_case_params(head, erased):
    """(mus, alphas, shifts) under which exactly ``erased`` miss any
    finite deadline: an infinite shift means a worker never responds."""
    mus, alphas, shifts = head.executor.worker_params
    dead = np.isin(np.arange(head.plan.num_workers), erased)
    return mus, alphas, jnp.asarray(np.where(dead, np.inf, shifts), jnp.float32)


def coded_logits_check(server, trace, erased, log) -> dict:
    """Decoded vs plain logits through ``Server._coded_select`` on the
    trace's prompts: (a) every worker finishes, (b) ``erased`` miss."""
    model, head = server.model, server.coded_head
    vocab = model.config.vocab_size
    tokens, lengths = _pad_prompts(trace)
    plain, _, _ = jax.jit(model.prefill)(server.params, tokens, lengths)
    lost = int(sum(head.plan.loads_per_worker[w] for w in erased))
    require(lost <= head.nb - head.kb,
            f"erasure pattern {erased} loses {lost} blocks, plan tolerates "
            f"{head.nb - head.kb}")
    select = jax.jit(server._coded_select)
    never = jnp.float32(1e30)  # deadline every finite round time meets
    out = {}
    for case, dead in (("all_finish", ()), ("erased", tuple(erased))):
        dec, (ok, _) = select(plain, jax.random.PRNGKey(0), never,
                              erasure_case_params(head, dead))
        require(bool(ok), f"coded decode not ok with workers {dead} erased")
        stats = compare_logits(dec, plain, vocab)
        log(f"coded vs plain logits [{case}: workers {list(dead)} erased, "
            f"{lost if dead else 0} blocks lost]: max_abs_err="
            f"{stats['max_abs_err']!r} bound={stats['bound']!r} "
            f"greedy {stats['greedy_agree']}/{stats['greedy_checked']}")
        require(stats["ok"], f"coded logits off the plain ones ({case}): "
                f"{stats}")
        out[case] = stats
    return out


def kernel_check(server, log) -> dict:
    """The serve path's two Pallas kernels against their jax routes:
    the coded-matvec kernel in both coded-head product paths, and the
    paged decode attention at the model's widths."""
    head = server.coded_head
    c = server.model.config
    key = jax.random.PRNGKey(1)
    out = {}
    t0 = time.perf_counter()
    jax.block_until_ready(head.coded)
    out["coded_table_s"] = time.perf_counter() - t0
    log(f"coded table {tuple(head.coded.shape)} built on the device in "
        f"{out['coded_table_s']!r} s (first use, by worker_products)")
    h = jax.random.normal(key, (4, c.d_model), jnp.float32)
    logits = h @ head.table.T
    for name, fn in (("worker_products", lambda k: head.worker_products(h, use_kernel=k)),
                     ("encode_logits", lambda k: head.encode_logits(logits, use_kernel=k))):
        ref, ker = np.asarray(fn(False)), np.asarray(fn(True))
        err = float(np.max(np.abs(ker - ref)))
        scale = float(np.max(np.abs(ref)))
        log(f"coded_matvec kernel [{name} {ref.shape}]: max_abs_err={err!r} "
            f"(max|ref|={scale!r})")
        require(err <= 1e-5 * scale, f"coded_matvec kernel off ({name})")
        out[name] = err
    # paged decode attention: 4 slots, 8 KV heads x 128, blocks of 16
    s, bl, mb = 4, 16, 8
    kv, g, hd = c.num_kv_heads, c.num_heads // c.num_kv_heads, c.resolved_head_dim
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (s, kv, g, hd), jnp.bfloat16)
    k_pool = jax.random.normal(ks[1], (s * mb + 1, bl, kv * hd), jnp.bfloat16)
    v_pool = jax.random.normal(ks[2], (s * mb + 1, bl, kv * hd), jnp.bfloat16)
    rng = np.random.default_rng(0)
    table = rng.permutation(s * mb).reshape(s, mb).astype(np.int32)
    table[1, 5:] = -1
    pos = np.array([127, 70, 3, 100], np.int32)
    ref = pa.paged_decode_attend(q, k_pool, v_pool, table, pos)
    ker = pa.paged_decode_attend_kernel(q, k_pool, v_pool, table, pos)
    err = float(jnp.max(jnp.abs(ker.astype(jnp.float32) - ref.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
    log(f"paged_decode kernel [{tuple(q.shape)} pool {tuple(k_pool.shape)}]: "
        f"max_abs_err={err!r} (max|ref|={scale!r})")
    # bfloat16 outputs: two ulps of the largest value
    require(err <= 2.0 ** -6 * scale, "paged decode kernel off the gather path")
    out["paged_decode"] = err
    return out


def serve_phase(config, *, num_requests: int = 8, slots: int = 4,
                seed: int = 0, erased=ERASED_WORKERS, kernels: bool = True,
                log=print) -> dict:
    """Serve a seeded chat trace through the coded head, then check it."""
    clock = CompileClock()
    t0 = time.perf_counter()
    model = Model(config)
    params = jax.block_until_ready(
        jax.jit(model.init_params)(jax.random.PRNGKey(seed))
    )
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    server = Server(model, params, ClusterSpec.parse(GROUPS), ServeConfig())
    head_s = time.perf_counter() - t0
    head = server.coded_head
    log(f"model {config.name}: {model.param_count()} params, "
        f"{config.num_layers} layers, d_model {config.d_model}, "
        f"vocab {config.vocab_size}")
    log(f"coded head: (nb, kb)=({head.nb}, {head.kb}) blocks of "
        f"{head.block_rows} rows, loads/worker="
        f"{head.plan.loads_per_worker.tolist()}")

    trace = make_workload("chat", num_requests=num_requests,
                          vocab=config.vocab_size).trace(seed=seed)
    before = clock.seconds
    rep = server.serve(trace, slots=slots)
    compile_s = clock.seconds - before
    traces = server.serve_traces
    log(f"set-up s: init={init_s!r} coded_head={head_s!r} "
        f"first_compile={compile_s!r}")
    done = sum(f.outcome == "done" for f in rep.finished)
    log(f"serve: {done} done, {rep.shed} shed of {len(trace)} requests, "
        f"{rep.tokens} tokens, {rep.decode_rounds} decode + "
        f"{rep.prefill_rounds} prefill rounds, fallback_rounds="
        f"{rep.fallback_rounds}, serve_traces={traces}, "
        f"wall_s={rep.wall_s!r} (compile included)")
    require(done + rep.shed == len(trace), "requests lost by the scheduler")
    require(done > 0 and rep.tokens > 0, "no request was served")
    require(rep.tokens == sum(f.tokens for f in rep.finished
                              if f.outcome == "done"), "token count mismatch")

    warm = server.serve(trace, slots=slots)
    log(f"warm serve: {warm.tokens} tokens, fallback_rounds="
        f"{warm.fallback_rounds}, serve_traces={server.serve_traces}, "
        f"wall_s={warm.wall_s!r}")
    require(server.serve_traces == traces, "warm serve retraced")
    require(warm.tokens == rep.tokens, "warm serve emitted other tokens")

    out = {
        "init_s": init_s, "coded_head_s": head_s, "first_compile_s": compile_s,
        "serve_traces": traces, "done": done, "shed": rep.shed,
        "tokens": rep.tokens, "fallback_rounds": rep.fallback_rounds,
        "coded_logits": coded_logits_check(server, trace, erased, log),
    }
    if kernels:
        out["kernels"] = kernel_check(server, log)
    return out


def four_chip_phase(devices, *, rows: int = 152_064, d: int = 1024,
                    block_rows: int = 256, erased=ERASED_WORKERS, seed: int = 0,
                    log=print) -> dict:
    """Distributed coded matvec over a ``workers`` mesh of ``devices``,
    against the same decode on the first device and the plain A @ x."""
    kb = rows // block_rows
    ex = CodedRoundExecutor(ClusterSpec.parse(GROUPS), kb, "optimal")
    plan, gen = ex.plan, np.asarray(ex.generator())
    w = plan.num_workers
    require(w % len(devices) == 0,
            f"{w} workers do not split evenly over {len(devices)} devices")
    ka, kx = jax.random.split(jax.random.PRNGKey(seed))
    a = 0.02 * jax.random.normal(ka, (kb, block_rows, d), jnp.float32)
    x = jax.random.normal(kx, (d,), jnp.float32)
    packed, row_of = pack_coded_matrix(gen, a, plan)
    mesh = Mesh(np.array(devices), ("workers",))
    mesh1 = Mesh(np.array(devices[:1]), ("workers",))
    packed_n = jax.device_put(packed, NamedSharding(mesh, P("workers")))
    packed_1 = jax.device_put(packed, NamedSharding(mesh1, P("workers")))
    del packed
    shards = packed_n.addressable_shards
    per_dev = {s.device: s.data.shape for s in shards}
    log(f"packed {tuple(packed_n.shape)} over {len(devices)} devices: "
        + ", ".join(f"{dev.id}:{shape}" for dev, shape in per_dev.items()))
    require(len(shards) == len(devices) and set(per_dev) == set(devices),
            "packed matrix is not one shard per device")
    require(all(shape[0] == w // len(devices) for shape in per_dev.values()),
            "uneven worker shards")
    plain = np.asarray(jnp.einsum("krd,d->kr", a, x,
                                  precision=jax.lax.Precision.HIGHEST)).ravel()
    bound = REL_TOL * float(np.max(np.abs(plain)))
    lost = int(sum(plan.loads_per_worker[i] for i in erased))
    require(lost <= plan.n - plan.k, "erasure pattern beyond the plan")
    out = {"n": plan.n, "k": plan.k, "bound": bound}
    for case, dead in (("all_finish", ()), ("erased", tuple(erased))):
        fin = jnp.asarray(~np.isin(np.arange(w), dead))
        z_n, ok_n = DecodePipeline(mesh, gen, row_of)(packed_n, x, fin)
        z_1, ok_1 = DecodePipeline(mesh1, gen, row_of)(packed_1, x, fin)
        require(bool(ok_n) and bool(ok_1), f"decode not ok ({case})")
        z_n, z_1 = np.asarray(z_n).ravel(), np.asarray(z_1).ravel()
        err_n = float(np.max(np.abs(z_n - plain)))
        err_1 = float(np.max(np.abs(z_1 - plain)))
        err_n1 = float(np.max(np.abs(z_n - z_1)))
        log(f"coded matvec [{case}: workers {list(dead)} erased]: "
            f"|{len(devices)}chip - A@x|={err_n!r} |1chip - A@x|={err_1!r} "
            f"|{len(devices)}chip - 1chip|={err_n1!r} bound={bound!r}")
        require(max(err_n, err_1, err_n1) <= bound, f"coded matvec off ({case})")
        out[case] = {"err_mesh": err_n, "err_one": err_1, "err_mesh_one": err_n1}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed coded matvec on 4 chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    log = lambda msg: print(msg, flush=True)
    log(f"compile cache: {enable_persistent_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    if args.four_chips:
        require(len(devices) == 4, f"--four-chips needs 4 chips, found "
                f"{len(devices)}")
        four_chip_phase(devices, log=log)
    else:
        serve_phase(get_arch(ARCH), log=log)
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
