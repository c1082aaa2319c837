"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the very first two lines (jax locks the device count on first
init; smoke tests and benches must keep seeing 1 device, so this flag is
set here and ONLY here):
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCHS, SHAPES_BY_NAME, get_arch, shapes_for  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.core.engine import CodedComputeEngine  # noqa: E402
from repro.core.runtime_model import ClusterSpec  # noqa: E402
from repro.core.schemes import make_scheme, scheme_names  # noqa: E402
from repro.data.pipeline import make_batch_specs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.model import Model, padded_vocab  # noqa: E402
from repro.optim import AdamWConfig, adamw_init  # noqa: E402
from repro.runtime.train_loop import make_train_step_fn  # noqa: E402
from repro.sharding import (  # noqa: E402
    make_batch_sharding,
    make_cache_sharding,
    make_param_sharding,
)

# TPU v5e hardware constants (roofline targets; see EXPERIMENTS.md).
PEAK_FLOPS = 197e12  # bf16 FLOP/s per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_COLLECTIVE_RE = re.compile(
    r"=\s*([a-z0-9\[\],\{\} ]+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO result type like 'bf16[8,128]{1,0}' (or tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved through collectives, by op kind.

    Shapes in the partitioned module are per-device; the RESULT size of
    each collective is used (for all-gather this upper-bounds the operand
    by the axis size — conservative in the right direction for a
    bandwidth bound).
    """
    out: dict = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
                 "all-to-all": 0, "collective-permute": 0, "count": 0}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        out[m.group(2)] += _shape_bytes(m.group(1))
        out["count"] += 1
    out["total"] = sum(v for k, v in out.items() if k not in ("count", "total"))
    return out


def analytic_inner_costs(config: ModelConfig, shape: ShapeConfig) -> dict:
    """Analytic FLOPs/bytes of INNER scanned loops (counted once by XLA).

    HLO cost analysis counts a while-loop body once; layer stacking is
    fixed by unrolling/delta-compiles, but the flash-attention q/kv block
    scans, the Mamba2 chunk scan and the xLSTM time scan remain while
    loops inside a single layer. Their work is added analytically:

    * attention:  4*B*H*Sq*Skv*hd fwd (scores + AV, both sides of the
      softmax); x3 for train (backward ~2x fwd) + x1 remat recompute.
      Baseline computes masked causal blocks, so Skv is NOT halved.
      bytes: flash streams K,V once per q block: nq * Skv * KV * hd * 2.
    * mamba2: 2*B*S*(Q*d_inner + Q*N + 2*N*d_inner) fwd per layer.
    * xlstm: mLSTM 4*B*S*d_in*hd + sLSTM 8*B*S*d*hd fwd per layer.

    Decode cells have no inner scans (single-token attention is a plain
    einsum over the cache) -> zero correction.
    """
    c = config
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    train_mult = 4.0 if shape.kind == "train" else 1.0  # fwd+remat+~2x bwd
    bytes_dt = 2  # bf16 compute
    flops = 0.0
    byts = 0.0
    hd = c.resolved_head_dim
    if c.family in ("dense", "vlm", "moe", "audio"):
        n_attn_layers = c.num_layers + (
            c.num_encoder_layers if c.family == "audio" else 0
        )
        sq = s + (c.num_image_tokens if c.family == "vlm" else 0)
        skv_eff = min(c.sliding_window or sq, sq)
        if c.causal_block_skip:  # lower-triangular iteration: ~half
            skv_eff = skv_eff / 2.0 + min(c.attn_kv_block, sq) / 2.0
        nq = max(sq // min(c.attn_q_block, sq), 1)
        flops += n_attn_layers * 4.0 * b * c.num_heads * sq * skv_eff * hd
        byts += (n_attn_layers * nq * skv_eff * c.num_kv_heads * hd
                 * 2 * bytes_dt * b)
        if c.family == "audio":  # cross-attention to encoder frames
            flops += c.num_layers * 4.0 * b * c.num_heads * s * c.encoder_seq * hd
    if c.family == "hybrid":
        d_inner = c.mamba_expand * c.d_model
        q = c.mamba_chunk
        flops += c.num_layers * 2.0 * b * s * (
            q * d_inner + q * c.ssm_state + 2 * c.ssm_state * d_inner
        )
        n_inv = -(-c.num_layers // max(c.attn_every, 1))
        flops += n_inv * 4.0 * b * c.num_heads * s * s * hd
        byts += n_inv * (s // min(c.attn_q_block, s)) * s * c.num_kv_heads * hd \
            * 2 * bytes_dt * b
    if c.family == "ssm":  # xLSTM time scans
        d_in = int(c.d_model * c.proj_factor)
        hd_x = d_in // c.num_heads
        n_s = sum(
            1 for i in range(c.num_layers)
            if c.slstm_every and (i + 1) % c.slstm_every == 0
        )
        n_m = c.num_layers - n_s
        flops += n_m * 4.0 * b * s * d_in * hd_x
        flops += n_s * 8.0 * b * s * c.d_model * (c.d_model // c.num_heads)
    return {"flops": flops * train_mult, "bytes": byts * train_mult}


def coded_head_record(config: ModelConfig, cluster: ClusterSpec, *,
                      scheme="optimal", block_rows: int = 256) -> dict:
    """Closed-form coded-LM-head deployment stats for one arch (no compile).

    Uses the same ``CodedComputeEngine`` path the serving loop deploys:
    kb vocab blocks of ``block_rows`` rows (ceil, matching CodedLMHead),
    MDS-coded over the cluster under the requested registered scheme
    (name or AllocationScheme object).
    """
    kb = -(-padded_vocab(config.vocab_size) // block_rows)
    eng = CodedComputeEngine(cluster, kb, scheme)
    return {
        "scheme": eng.plan.scheme,
        "block_rows": block_rows,
        "kb": kb,
        "nb": eng.plan.n,
        "rate": eng.plan.rate,
        "workers": eng.plan.num_workers,
        "max_blocks_per_worker": eng.plan.max_load,
        "t_star": eng.t_star,
        "deadline": eng.deadline(),
    }


def _parse_cluster(groups: str, bandwidth: float | None = None) -> ClusterSpec:
    """'6:2.0,6:0.5[:bw]' -> ClusterSpec (same syntax as launch/serve.py)."""
    return ClusterSpec.parse(groups, bandwidth)


def model_flops(config: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6 N D (train) / 2 N_active per token (decode)."""
    m = Model(config)
    n_active = m.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token/seq


def _abstract_train_inputs(model: Model, shape: ShapeConfig, mesh,
                           strategy: str = "2d"):
    params_s = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(
        moment_dtype="bfloat16" if model.param_count() > 5e10 else "float32"
    )
    opt_s = jax.eval_shape(lambda p: adamw_init(opt_cfg, p), params_s)
    batch_s = make_batch_specs(model.config, shape)
    include_model = strategy == "replicated"
    shardings = (
        make_param_sharding(mesh, params_s, strategy=strategy),
        make_param_sharding(mesh, opt_s, strategy=strategy),
        make_batch_sharding(mesh, batch_s, include_model=include_model),
    )
    return (params_s, opt_s, batch_s), shardings, opt_cfg


def _abstract_prefill_inputs(model: Model, shape: ShapeConfig, mesh,
                             strategy: str = "2d"):
    """Prefill = full forward over (B, S) producing logits."""
    c = model.config
    params_s = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    b, s = shape.global_batch, shape.seq_len
    tok_s = jax.ShapeDtypeStruct((b, s), jnp.int32)
    extras_s = None
    if c.family == "vlm":
        extras_s = {
            "image_embeds": jax.ShapeDtypeStruct(
                (b, c.num_image_tokens, c.d_model), c.cdtype
            )
        }
    if c.family == "audio":
        extras_s = {
            "frames": jax.ShapeDtypeStruct((b, c.encoder_seq, c.d_model), c.cdtype)
        }
    batch_tree = {"tokens": tok_s}
    if extras_s:
        batch_tree["extras"] = extras_s
    if strategy == "dp_seq":
        # context parallelism: params replicated, batch on data axes and
        # SEQUENCE on the model axis — tiny-model long-context prefill
        # keeps full 256-way work partitioning with only k/v gathers.
        from jax.sharding import NamedSharding, PartitionSpec as P

        def seq_shard(path, leaf):
            if len(leaf.shape) == 2:  # (B, S) tokens
                return NamedSharding(mesh, P("data", "model"))
            return NamedSharding(mesh, P("data", *([None] * (len(leaf.shape) - 1))))

        shardings = (
            make_param_sharding(mesh, params_s, strategy="replicated"),
            jax.tree_util.tree_map_with_path(seq_shard, batch_tree),
        )
        return (params_s, batch_tree), shardings
    shardings = (
        make_param_sharding(mesh, params_s, strategy=strategy),
        make_batch_sharding(mesh, batch_tree,
                            include_model=strategy == "replicated"),
    )
    return (params_s, batch_tree), shardings


def _abstract_decode_inputs(model: Model, shape: ShapeConfig, mesh):
    c = model.config
    params_s = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    b = shape.global_batch
    extras = None
    if c.family == "audio":
        extras = {
            "enc_out": jax.ShapeDtypeStruct((b, c.encoder_seq, c.d_model), c.cdtype)
        }
    if extras is None:
        cache_s = jax.eval_shape(lambda: model.init_cache(b, shape.seq_len))
    else:
        cache_s = jax.eval_shape(
            lambda e: model.init_cache(b, shape.seq_len, e), extras
        )
    tok_s = jax.ShapeDtypeStruct((b,), jnp.int32)
    pos_s = jax.ShapeDtypeStruct((), jnp.int32)
    from jax.sharding import NamedSharding, PartitionSpec as P

    shardings = (
        make_param_sharding(mesh, params_s),
        make_cache_sharding(mesh, cache_s),
        NamedSharding(mesh, P(None)),
        NamedSharding(mesh, P()),
    )
    return (params_s, cache_s, tok_s, pos_s), shardings


def dryrun_cell(config: ModelConfig, shape: ShapeConfig, *, multi_pod: bool,
                verbose: bool = True, with_hlo: bool = False,
                scan_layers: bool = False, donate_cache: bool = False,
                param_strategy: str = "2d") -> dict:
    """Lower + compile one cell; return the roofline record.

    scan_layers=False (default): layers unrolled so cost_analysis and the
    collective-bytes parse see every layer (XLA counts a while body once).
    donate_cache: alias the decode KV cache in/out (in-place update).
    param_strategy: "2d" (FSDP+TP) or "replicated" (pure DP) — §Perf.
    """
    config = dataclasses.replace(config, scan_layers=scan_layers)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    model = Model(config)
    # perf_counter, not time.time(): wall clock is not monotonic (an NTP
    # step mid-compile would report a negative/garbage compile_s)
    t0 = time.perf_counter()

    if shape.kind == "train":
        (p_s, o_s, b_s), shardings, opt_cfg = _abstract_train_inputs(
            model, shape, mesh, strategy=param_strategy)
        step = make_train_step_fn(model, opt_cfg)
        jitted = jax.jit(step, in_shardings=shardings)
        with mesh:
            lowered = jitted.lower(p_s, o_s, b_s)
    elif shape.kind == "prefill":
        (p_s, b_s), shardings = _abstract_prefill_inputs(
            model, shape, mesh, strategy=param_strategy)

        def prefill_step(params, batch):
            return model.lm_logits(params, batch["tokens"], batch.get("extras"))

        jitted = jax.jit(prefill_step, in_shardings=shardings)
        with mesh:
            lowered = jitted.lower(p_s, b_s)
    else:
        (p_s, c_s, t_s, pos_s), shardings = _abstract_decode_inputs(model, shape, mesh)
        donate = {"donate_argnums": (1,)} if donate_cache else {}
        jitted = jax.jit(model.decode_step, in_shardings=shardings, **donate)
        with mesh:
            lowered = jitted.lower(p_s, c_s, t_s, pos_s)

    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_rec = {"error": str(e)}

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    mflops = model_flops(config, shape)
    inner = analytic_inner_costs(config, shape)
    flops_c = flops_dev + inner["flops"] / chips
    bytes_c = bytes_dev + inner["bytes"] / chips

    record = {
        "arch": config.name,
        "shape": shape.name,
        "kind": shape.kind,
        "scan_layers": scan_layers,
        "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        "chips": chips,
        "compile_seconds": round(compile_s, 1),
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "inner_scan_correction": inner,
        "flops_per_device_corrected": flops_c,
        "bytes_per_device_corrected": bytes_c,
        "collective_bytes_per_device": coll,
        "memory_analysis": mem_rec,
        "model_flops": mflops,
        # --- roofline terms (seconds; inner-scan-corrected) ---
        "t_compute": flops_c / PEAK_FLOPS,
        "t_memory": bytes_c / HBM_BW,
        "t_collective": coll["total"] / ICI_BW,
        "useful_flops_ratio": mflops / max(flops_c * chips, 1.0),
    }
    terms = {k: record[k] for k in ("t_compute", "t_memory", "t_collective")}
    record["bottleneck"] = max(terms, key=terms.get)
    record["roofline_fraction"] = (
        record["t_compute"] / max(max(terms.values()), 1e-30)
    )
    if with_hlo:
        record["hlo_text"] = hlo
    if verbose:
        print(
            f"[dryrun] {config.name:24s} {shape.name:12s} {record['mesh']:20s} "
            f"compile={compile_s:6.1f}s flops/dev={flops_dev:.3e} "
            f"bytes/dev={bytes_dev:.3e} coll/dev={coll['total']:.3e} "
            f"bottleneck={record['bottleneck']}"
        )
    return record


def _raw_costs(config: ModelConfig, shape: ShapeConfig, *, multi_pod: bool,
               **cell_kwargs):
    """(flops, bytes, coll_total, compile_s) per device for one compile."""
    rec = dryrun_cell(config, shape, multi_pod=multi_pod, verbose=False,
                      scan_layers=config.scan_layers, **cell_kwargs)
    return (
        rec["hlo_flops_per_device"],
        rec["hlo_bytes_per_device"],
        rec["collective_bytes_per_device"]["total"],
        rec["compile_seconds"],
        rec,
    )


def roofline_cell(config: ModelConfig, shape: ShapeConfig, *,
                  multi_pod: bool = False, verbose: bool = True,
                  **cell_kwargs) -> dict:
    """Roofline record via the per-layer finite-difference method.

    XLA counts a scanned (while-loop) body once and fully-unrolled
    compiles of the 40-64-layer archs are prohibitive on this CPU host,
    so per-layer costs are measured EXACTLY by compiling the same
    (shape x mesh x sharding) cell at 1 and 2 layers (python-unrolled)
    and differencing:  total = cost(1L) + (num_layers - 1) * delta.
    Small archs (ssm/audio: layers are python loops anyway) compile
    fully unrolled directly. Validated against full unrolls of yi-9b
    prefill and xlstm train (EXPERIMENTS.md §Roofline methodology).
    """
    c = dataclasses.replace(config, scan_layers=False)
    small_families = ("ssm", "audio")
    if c.family in small_families or c.num_layers <= 4:
        rec = dryrun_cell(c, shape, multi_pod=multi_pod, verbose=False,
                          scan_layers=False, **cell_kwargs)
        rec["method"] = "full_unroll"
        if verbose:
            _print_roofline(rec)
        return rec

    if c.family == "hybrid":
        no_attn = 10 ** 6
        base = _raw_costs(
            dataclasses.replace(c, num_layers=1, attn_every=no_attn), shape,
            multi_pod=multi_pod, **cell_kwargs)
        two = _raw_costs(
            dataclasses.replace(c, num_layers=2, attn_every=no_attn), shape,
            multi_pod=multi_pod, **cell_kwargs)
        attn1 = _raw_costs(
            dataclasses.replace(c, num_layers=1, attn_every=1), shape,
            multi_pod=multi_pod, **cell_kwargs)
        n_inv = -(-c.num_layers // max(c.attn_every, 1))
        d_layer = tuple(two[i] - base[i] for i in range(3))
        d_attn = tuple(attn1[i] - base[i] for i in range(3))
        flops, byts, coll = (
            base[i] + (c.num_layers - 1) * d_layer[i] + n_inv * d_attn[i]
            for i in range(3)
        )
        compile_s = base[3] + two[3] + attn1[3]
        proto = base[4]
    else:  # dense / moe / vlm — homogeneous stacks
        # MoE modules show +-1.5e12 FLOP jitter between compiles (XLA
        # fusion decisions around the sort-based dispatch), which swamps
        # a 1-layer delta; widen the spacing so the jitter amortizes.
        l_lo, l_hi = (2, 8) if c.family == "moe" else (1, 2)
        base = _raw_costs(dataclasses.replace(c, num_layers=l_lo), shape,
                          multi_pod=multi_pod, **cell_kwargs)
        hi = _raw_costs(dataclasses.replace(c, num_layers=l_hi), shape,
                        multi_pod=multi_pod, **cell_kwargs)
        span = l_hi - l_lo
        d_layer = tuple((hi[i] - base[i]) / span for i in range(3))
        flops, byts, coll = (
            max(base[i] + (c.num_layers - l_lo) * d_layer[i], base[i])
            for i in range(3)
        )
        compile_s = base[3] + hi[3]
        proto = base[4]

    mflops = model_flops(config, shape)
    chips = proto["chips"]
    inner = analytic_inner_costs(config, shape)
    flops_c = flops + inner["flops"] / chips
    bytes_c = byts + inner["bytes"] / chips
    record = dict(proto)
    record.update(
        arch=config.name,
        method="layer_delta",
        compile_seconds=round(compile_s, 1),
        hlo_flops_per_device=flops,
        hlo_bytes_per_device=byts,
        inner_scan_correction=inner,
        flops_per_device_corrected=flops_c,
        bytes_per_device_corrected=bytes_c,
        collective_bytes_per_device={"total": coll},
        model_flops=mflops,
        t_compute=flops_c / PEAK_FLOPS,
        t_memory=bytes_c / HBM_BW,
        t_collective=coll / ICI_BW,
        useful_flops_ratio=mflops / max(flops_c * chips, 1.0),
    )
    terms = {k: record[k] for k in ("t_compute", "t_memory", "t_collective")}
    record["bottleneck"] = max(terms, key=terms.get)
    record["roofline_fraction"] = record["t_compute"] / max(
        max(terms.values()), 1e-30
    )
    if verbose:
        _print_roofline(record)
    return record


def _print_roofline(r: dict):
    print(
        f"[roofline] {r['arch']:24s} {r['shape']:12s} {r['mesh']:20s} "
        f"method={r.get('method', '?'):12s} compile={r['compile_seconds']:6.1f}s "
        f"flops/dev={r['hlo_flops_per_device']:.3e} "
        f"bytes/dev={r['hlo_bytes_per_device']:.3e} "
        f"coll/dev={r['collective_bytes_per_device']['total']:.3e} "
        f"bottleneck={r['bottleneck']} frac={r['roofline_fraction']:.3f}"
    )


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape name (default: all)")
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="both")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--scan-layers", action="store_true",
                    help="compile with scanned layers (fast compile; use for "
                         "the multi-pod compilability proof — roofline terms "
                         "then undercount per-layer work)")
    ap.add_argument("--roofline", action="store_true",
                    help="use the per-layer finite-difference method for "
                         "accurate roofline terms (see roofline_cell)")
    ap.add_argument("--coded-groups", default=None,
                    help="N:mu worker groups; attaches the coded-LM-head "
                         "deployment record (CodedComputeEngine) to every "
                         "decode cell")
    ap.add_argument("--coded-scheme", default="optimal",
                    choices=scheme_names(),
                    help="registered allocation scheme for --coded-groups")
    ap.add_argument("--coded-n", type=float, default=None,
                    help="code size n for --coded-scheme uniform_n")
    ap.add_argument("--coded-r", type=int, default=None,
                    help="completion count r for --coded-scheme uniform_r")
    ap.add_argument("--coded-bandwidth", type=float, default=None,
                    help="link bandwidth for --coded-groups entries without "
                         "an explicit N:mu:bw value (default: infinite)")
    ap.add_argument("--coded-upload", type=float, default=None,
                    help="fixed transfer cost for --coded-scheme comm_aware "
                         "/ comm_uniform")
    ap.add_argument("--coded-download", type=float, default=None,
                    help="per-row transfer cost for --coded-scheme "
                         "comm_aware / comm_uniform")
    args = ap.parse_args()
    # resolve cluster + scheme up front so bad params fail before any compile
    coded_cluster = (
        _parse_cluster(args.coded_groups, args.coded_bandwidth)
        if args.coded_groups
        else None
    )
    coded_scheme = (
        make_scheme(args.coded_scheme, n=args.coded_n, r=args.coded_r,
                    upload=args.coded_upload, download=args.coded_download)
        if coded_cluster is not None
        else None
    )

    os.makedirs(args.out, exist_ok=True)
    archs = [get_arch(args.arch)] if args.arch else list(ARCHS.values())
    meshes = {"single": (False,), "multi": (True,), "both": (False, True)}[args.mesh]

    failures = []
    for cfg in archs:
        shapes = shapes_for(cfg)
        if args.shape:
            shapes = [s for s in shapes if s.name == args.shape]
            if not shapes and args.shape in SHAPES_BY_NAME:
                print(f"[dryrun] {cfg.name}: shape {args.shape} SKIPPED "
                      f"(not applicable; see DESIGN.md)")
        for shape in shapes:
            for mp in meshes:
                tag = f"{cfg.name}_{shape.name}_{'multi' if mp else 'single'}"
                if args.scan_layers:
                    tag += "_scanned"
                try:
                    if args.roofline:
                        rec = roofline_cell(cfg, shape, multi_pod=mp)
                    else:
                        rec = dryrun_cell(cfg, shape, multi_pod=mp,
                                          scan_layers=args.scan_layers)
                    if coded_cluster is not None and shape.kind == "decode":
                        rec["coded_lm_head"] = coded_head_record(
                            cfg, coded_cluster, scheme=coded_scheme
                        )
                    with open(os.path.join(args.out, tag + ".json"), "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"[dryrun] FAIL {tag}: {e!r}")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err[:200]}")
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
