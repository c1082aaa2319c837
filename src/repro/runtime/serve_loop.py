"""Serving loop with the paper's coded matvec as the LM-head path.

Decode-time logits are exactly the paper's workload: ``logits = E h``
with ``E in R^{V x D}`` (the tied embedding) and one ``h in R^D`` per
sequence — a matrix-vector product whose rows can be MDS-coded and
spread over heterogeneous workers.

Block-level MDS: V rows are padded into ``kb`` row-blocks of ``R`` rows;
an ``(nb, kb)`` MDS code over BLOCKS yields coded blocks
``E~_i = sum_j G[i, j] E_j``. Worker w stores ``l_w`` coded blocks (the
paper's load allocation, in block units) and returns the (R,)-per-block
products ``E~_i h``. Any ``kb`` coded block-products reconstruct all
logits — workers missing the deadline (T* x safety) are erasures.

Jit-native decode pipeline (DESIGN.md §4): the whole generation —
prefill, per-token decode, straggler-mask sampling, erasure decode and
the insufficient-survivors fallback — is ONE compiled program driven by
``jax.lax.scan``. The coded head precomputes its worker->block scatter
map at init, samples finish masks inside the jitted step from
``fold_in``'d keys, and decodes with the fixed-shape
``decode_systematic_jit``; nothing touches the host between tokens. The
legacy per-token host loop (numpy ``np.linalg.solve`` decode) survives
behind ``ServeConfig(jit_pipeline=False)`` as the reference/baseline
path for ``benchmarks/serve_throughput.py``.

Substrate integration: the head's per-round mechanics — plan, (nb, kb)
generator, deadline, straggler-mask sampling, worker->block scatter map,
replan hooks — come from the shared ``CodedRoundExecutor``
(``runtime/executor.py``, DESIGN.md §5), the same substrate the coded
trainer consumes, so the per-worker block counts follow the configured
``AllocationScheme`` (Theorem 2 by default; any registered scheme via
``ServeConfig.scheme``).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coding import decode_systematic_jit, encode, make_generator
from repro.core.planner import DeploymentPlan
from repro.core.runtime_model import ClusterSpec
from repro.core.schemes import AllocationScheme
from repro.models.model import DTYPES_LOGITS, Model, padded_vocab
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, SpanTracer
from repro.runtime.executor import CodedRoundExecutor
from repro.runtime.plan_bucket import BucketConfig

NEG_INF = -1e30  # pad-vocab sentinel (matches Model._mask_pad_logits)


@dataclasses.dataclass
class ServeConfig:
    block_rows: int = 256  # R: vocab rows per MDS block
    deadline_safety: float = 3.0
    max_decode_steps: int = 32
    scheme: str | AllocationScheme = "optimal"  # registry name or object
    use_kernel: bool = False  # Pallas coded-matvec kernel for the block mix
    jit_pipeline: bool = True  # False: legacy per-token host loop (numpy)
    # paged KV serving (DESIGN.md §13): ``serve`` runs on a block-pooled
    # cache with chunked prefill; ``paged=False`` keeps the dense
    # slot-cache path (the bit-parity oracle).
    paged: bool = True
    block_len: int = 16  # tokens per physical KV block
    num_blocks: int | None = None  # pool size; None = dense-equivalent auto
    prefill_chunk: int | None = None  # admission chunk; None = prompt_cap
    # plan bucketing (DESIGN.md §11): set ``bucket_quantum`` to quantize
    # integer loads onto bucket shapes and replan in-program via a
    # runtime bucket switch — intra-capacity replans then retrace nothing
    bucket_quantum: int | None = None
    bucket_capacity: int = 8
    bucket_headroom: float = 1.5

    def bucket_config(self) -> BucketConfig | None:
        if self.bucket_quantum is None:
            return None
        return BucketConfig(
            quantum=self.bucket_quantum,
            capacity=self.bucket_capacity,
            n_headroom=self.bucket_headroom,
        )


class CodedLMHead:
    """MDS-coded unembedding for straggler-tolerant decode.

    Per-round mechanics (deadline, straggler-mask sampling, worker->block
    scatter map, replan hooks) come from the shared
    ``CodedRoundExecutor`` — the same substrate the coded trainer runs
    on (DESIGN.md §5). The head adds the workload-specific parts: the
    coded vocab blocks and the logits encode/decode. All ``*_jit``
    methods are traceable and run under the server's single compiled
    generation program.
    """

    def __init__(self, embed_table, cluster: ClusterSpec, *, block_rows: int = 256,
                 key=None, scheme: str | AllocationScheme = "optimal",
                 deadline_safety: float = 3.0,
                 bucket_config: BucketConfig | None = None, telemetry=None):
        self.table = jnp.asarray(embed_table, jnp.float32)  # (Vp, D)
        vp, _ = self.table.shape
        self.block_rows = block_rows
        self.kb = -(-vp // block_rows)  # blocks needed to cover the vocab
        self.executor = CodedRoundExecutor(
            cluster, self.kb, scheme, deadline_safety=deadline_safety,
            bucket_config=bucket_config, telemetry=telemetry,
        )
        self.engine = self.executor.engine
        self._generator_key = key
        self.refresh()

    def refresh(self) -> None:
        """(Re)bind all plan-derived state to the executor's current plan.

        Called at init and after every executor replan (e.g. driven by an
        ``AdaptiveController``): the code size ``nb``, the generator, the
        coded vocab blocks, the deadline and the worker->block scatter
        map all depend on the deployed plan. Consumers holding programs
        traced against the old shapes must re-jit (``Server`` does via
        ``refresh_coded_head``).
        """
        self.plan: DeploymentPlan = self.executor.plan
        buckets = self.executor.buckets
        # Bucket mode codes at slot CAPACITY: the first n rows of the
        # systematic (n_cap, kb) code form a valid (n, kb) code and the
        # capacity padding rows are never alive, so ONE generator + coded
        # tensor serves every admitted bucket (rebuilt only on structural
        # replans, never on a bucket switch).
        self.nb = buckets.n_cap if buckets is not None else self.plan.n
        self.generator = np.asarray(
            make_generator(self.nb, self.kb, key=self._generator_key)
            if buckets is not None
            else self.executor.generator(key=self._generator_key)
        )
        self.generator_j = jnp.asarray(self.generator)
        self._coded = None  # built on first use (``coded``)
        self.deadline = self.executor.deadline
        self._rows_of_worker = self.plan.row_ranges  # block ranges per worker
        # worker->block scatter map: block_owner[i] = worker holding coded
        # block i, so a (W,) finish mask gathers to an (nb,) erasure mask
        # in one device op (no per-worker Python loop at decode time).
        self.block_owner = self.executor.slot_owner

    @property
    def coded(self):
        """Coded vocab blocks (nb, R, D): ``E~_i = sum_j G[i, j] E_j``.

        Only ``worker_products`` (computing from h) reads them; the serve
        path mixes logits instead (``encode_logits``). So the table —
        nb x R x D float32, about 0.9 GB at a 152k vocab — is encoded on
        the device on first use, not at every (re)plan.
        """
        if self._coded is None:
            pad = self.kb * self.block_rows - self.table.shape[0]
            blocks = jnp.pad(self.table, ((0, pad), (0, 0))).reshape(
                self.kb, self.block_rows, -1
            )
            self._coded = encode(self.generator_j, blocks)
        return self._coded

    def rebind_soft(self) -> None:
        """Rebind after a NON-structural bucket-switch replan.

        Shapes, generator and coded blocks are unchanged — compiled
        consumer programs stay valid, and the new branch state reaches
        them through ``executor.bucket_args()`` at the next dispatch.
        Only the cheap host-side plan views are refreshed here.
        """
        self.plan = self.executor.plan
        self.deadline = self.executor.deadline
        self._rows_of_worker = self.plan.row_ranges
        self.block_owner = self.executor.slot_owner

    def replan(self, new_cluster: ClusterSpec) -> DeploymentPlan:
        """Elastic replan + rebind (scheme params preserved by the engine)."""
        plan = self.executor.replan(new_cluster)
        if self.executor.last_replan_structural:
            self.refresh()
        else:
            self.rebind_soft()
        return plan

    # ------------------------------------------------------ jit pipeline
    def finish_mask_jit(self, key, deadline, *, mus=None, alphas=None,
                        shifts=None):
        """(W,) bool straggler mask, traceable (``CodedRoundExecutor``)."""
        return self.executor.finish_mask_jit(
            key, deadline, mus=mus, alphas=alphas, shifts=shifts
        )

    def encode_logits(self, logits, *, use_kernel: bool = False):
        """Mix plain logit BLOCKS with G: (B, V) -> (nb, B, R) products.

        Coded products are linear in the hidden state: (G (x) I_R) E h.
        Since logits = E h, mixing logit blocks with G is numerically
        identical to each worker computing E~_i h from h directly, so the
        erasure/decode path is exercised end-to-end without re-running
        the unembed matmul. The mix runs at full float32 precision: a
        TPU's default would round every product to bfloat16, and the
        erasure solve amplifies that. ``use_kernel`` routes the mix
        through the Pallas coded-matvec kernel, with the B x R logit
        columns as its batch of vectors.
        """
        b, v = logits.shape
        vp = self.kb * self.block_rows
        lf = jnp.pad(logits.astype(jnp.float32), ((0, 0), (0, vp - v)))
        blocks = lf.reshape(b, self.kb, self.block_rows)
        if use_kernel:
            from repro.kernels.coded_matvec import ops as cmv_ops

            cols = blocks.transpose(0, 2, 1).reshape(b * self.block_rows, self.kb)
            mixed = cmv_ops.blocked_matvec(self.generator_j, cols)  # (B*R, nb)
            return mixed.T.reshape(self.nb, b, self.block_rows)
        return jnp.einsum("nk,bkr->nbr", self.generator_j, blocks,
                          precision=jax.lax.Precision.HIGHEST)

    def decode_logits_jit(self, products, finished_workers):
        """Fixed-shape on-device decode: (nb, B, R) + (W,) -> ((B, kb*R), ok).

        The worker finish mask gathers through the precomputed scatter
        map to an (nb,) block-erasure mask; ``decode_systematic_jit``
        solves the static (kb, kb) system on-device. ``ok`` is a traced
        bool — the caller folds the insufficient-survivors fallback in
        with ``jnp.where`` instead of a Python branch.
        """
        alive = jnp.asarray(finished_workers, bool)[self.block_owner]
        nb, b, r = products.shape
        z, ok = decode_systematic_jit(
            self.generator_j, products.reshape(nb, b * r), alive
        )
        logits = z.reshape(self.kb, b, r).transpose(1, 0, 2).reshape(b, -1)
        return logits, ok

    def decode_logits_bucket_jit(self, products, alive_blocks):
        """``decode_logits_jit`` with a precomputed (nb,) block-alive mask.

        Bucket-switch path: the erasure mask comes from the selected
        bucket's owner/alive arrays (``slot_mask_bucket_jit`` — capacity
        padding rows always dead) instead of the static scatter map.
        """
        nb, b, r = products.shape
        z, ok = decode_systematic_jit(
            self.generator_j, products.reshape(nb, b * r),
            jnp.asarray(alive_blocks, bool),
        )
        logits = z.reshape(self.kb, b, r).transpose(1, 0, 2).reshape(b, -1)
        return logits, ok

    def worker_products(self, h, *, use_kernel: bool = False):
        """All coded block-products for a batch of hiddens h: (B, D).

        Returns (nb, B, R). In deployment each worker computes only its
        slice; here the full product is computed and the erasure mask is
        applied at decode time (deadline semantics — see DESIGN.md §3).
        ``use_kernel`` routes the per-worker matvec through the Pallas
        ``coded_matvec`` kernel.
        """
        hf = h.astype(jnp.float32)
        if use_kernel:
            from repro.kernels.coded_matvec import ops as cmv_ops

            flat = self.coded.reshape(-1, self.coded.shape[-1])  # (nb*R, D)
            out = cmv_ops.blocked_matvec(flat, hf)  # (B, nb*R)
            return out.reshape(-1, self.nb, self.block_rows).transpose(1, 0, 2)
        return jnp.einsum("nrd,bd->nbr", self.coded, hf,
                          precision=jax.lax.Precision.HIGHEST)

    # ------------------------------------------- host-side reference path
    def decode_logits(self, products, finished_workers) -> tuple[np.ndarray, bool]:
        """Recover (B, Vp) logits from surviving coded block-products.

        Numpy reference oracle for ``decode_logits_jit`` (and the legacy
        ``jit_pipeline=False`` serving path).
        """
        products = np.asarray(products)  # (nb, B, R)
        fin = np.asarray(finished_workers, bool)
        alive_blocks = np.zeros((self.nb,), bool)
        for w, (s, e) in enumerate(self._rows_of_worker):
            if fin[w]:
                alive_blocks[s:e] = True
        if alive_blocks.sum() < self.kb:
            return np.zeros((products.shape[1], self.kb * self.block_rows)), False
        use = np.flatnonzero(alive_blocks)[: self.kb]
        g = self.generator[use]  # (kb, kb)
        y = products[use]  # (kb, B, R)
        z = np.linalg.solve(g, y.reshape(self.kb, -1)).reshape(self.kb, *y.shape[1:])
        logits = z.transpose(1, 0, 2).reshape(products.shape[1], -1)
        return logits, True

    def sample_finish_mask(self, key) -> np.ndarray:
        """Simulate which workers meet the deadline (shifted-exp model)."""
        return np.asarray(self.finish_mask_jit(key, self.deadline))


@dataclasses.dataclass(frozen=True)
class ServeReport:
    """Result of one ``Server.serve`` run over a request trace.

    Latencies and the clock are in ROUNDS (the serve loop's virtual
    clock: one slot-decode step = one round, one batched admit/prefill
    pass = one round) so scheduling outcomes are deterministic and
    CI-stable; ``wall_s`` is the measured wall time of the whole run for
    tokens/s comparisons.
    """

    finished: tuple  # FinishedRequest records, completion order
    tokens: int  # useful tokens emitted (done requests only)
    rounds: float  # final virtual-clock value
    decode_rounds: int
    prefill_rounds: int
    admitted: int
    shed: int
    wall_s: float
    #: decode rounds whose coded head lacked survivors to decode, so the
    #: plain logits stood in (``_coded_select``'s ``ok`` was False)
    fallback_rounds: int = 0

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / self.wall_s if self.wall_s > 0 else float("inf")

    def latencies(self) -> np.ndarray:
        """Arrival-to-last-token latencies (rounds) of DONE requests."""
        return np.asarray(
            [f.latency for f in self.finished if f.outcome == "done"], float
        )

    def latency_percentile(self, q: float) -> float:
        lat = self.latencies()
        return float(np.percentile(lat, q)) if lat.size else float("nan")


def _count_fallbacks(decode_oks) -> int:
    """Rounds with ``ok`` False over a run's per-dispatch flag arrays
    (read once, after the run, so dispatches never wait on the host)."""
    if not decode_oks:
        return 0
    return int(jnp.sum(~jnp.concatenate(decode_oks)))


class Server:
    """Batched decode with an optional coded LM head.

    The default path compiles a whole ``generate`` call — prefill scan,
    decode scan, coded erasure decode per token — into one XLA program;
    ``self.traces`` counts (re)traces so tests can assert that repeat
    calls with the same shapes never re-enter Python between tokens.

    ``serve`` is the continuous-batching mode (DESIGN.md §10): a
    slot-resident decode state driven by a ``SlotScheduler``, where
    request admits/evicts are pure buffer updates into ONE fused
    fixed-shape compiled program — a ``lax.cond``-gated prefill splice
    followed by a decode chunk (``serve_traces`` counts its (re)traces,
    one per distinct chunk size — slot swaps must not add any).
    """

    def __init__(self, model: Model, params, cluster: ClusterSpec | None = None,
                 cfg: ServeConfig | None = None):
        self.model = model
        self.params = params
        self.cfg = cfg or ServeConfig()
        self.coded_head = (
            CodedLMHead(
                params["embed"]["table"], cluster,
                block_rows=self.cfg.block_rows,
                scheme=self.cfg.scheme,
                deadline_safety=self.cfg.deadline_safety,
                bucket_config=self.cfg.bucket_config(),
            )
            if cluster is not None
            else None
        )
        self._decode = jax.jit(model.decode_step)
        self._prefill_fn = jax.jit(self._prefill_into_cache)
        self.traces = 0
        self.serve_traces = 0
        #: span tracer (§14); ``serve(tracer=...)`` rebinds it, and the
        #: no-op default keeps the untraced hot path allocation-free
        self.tracer = NULL_TRACER
        #: optional ground-truth (mus_w, alphas_w, shift_w) the next
        #: generate call samples straggling from (scenario closed loop)
        self._true_params = None
        #: the ClusterSpec behind _true_params (RoundClock decomposition
        #: needs the spec, not the flattened arrays)
        self._true_cluster = None
        self._generate_fn = jax.jit(
            self._gen_program, static_argnames=("max_new",)
        )
        # cache/logits/pos are donated: the serve loop threads them
        # through every dispatch and never reuses the old buffers, so XLA
        # can update the KV cache in place instead of copying it per call
        self._serve_step_fn = jax.jit(
            self._serve_step_program, static_argnames=("steps",),
            donate_argnums=(1, 2, 3),
        )
        self._serve_step_paged_fn = jax.jit(
            self._serve_step_paged_program, static_argnames=("steps",),
            donate_argnums=(1, 2, 3),
        )

    # --------------------------------------------------------- adaptivity
    def set_true_cluster(self, cluster: ClusterSpec | None) -> None:
        """Sample the NEXT generate call's straggling from this cluster.

        The scenario layer's ground truth: the coded head keeps planning
        against whatever the controller believes, but the in-program
        finish masks draw from the true cluster's parameters (leavers
        never respond, parameter drift shows up as missed deadlines).
        ``None`` restores sampling from the plan's own cluster.
        """
        if self.coded_head is None:
            raise ValueError("set_true_cluster requires a coded head")
        self._true_params = (
            None if cluster is None
            else self.coded_head.executor.worker_param_arrays(cluster)
        )
        self._true_cluster = cluster

    def refresh_coded_head(self) -> None:
        """Rebind the head to its executor's current plan and re-jit.

        The ``AdaptiveController.on_replan`` hook for serving: a replan
        changes the code size and scatter map, which are closure
        constants of the compiled generation program, so the jit cache
        must be dropped (the retrace IS the serve-side replan cost the
        controller's cost model charges for).

        Bucket-switch mode: after a NON-structural replan the compiled
        programs are still valid — the new branch state reaches them as
        runtime arguments — so only the cheap host views rebind and the
        jit caches survive (the whole point of DESIGN.md §11).
        """
        if self.coded_head is None:
            raise ValueError("refresh_coded_head requires a coded head")
        if not self.coded_head.executor.last_replan_structural:
            self.coded_head.rebind_soft()
            self._true_params = None  # possibly stale after any replan
            self._true_cluster = None
            return
        self.coded_head.refresh()
        self._true_params = None  # stale shapes after a replan
        self._true_cluster = None
        self._generate_fn = jax.jit(
            self._gen_program, static_argnames=("max_new",)
        )
        # cache/logits/pos are donated: the serve loop threads them
        # through every dispatch and never reuses the old buffers, so XLA
        # can update the KV cache in place instead of copying it per call
        self._serve_step_fn = jax.jit(
            self._serve_step_program, static_argnames=("steps",),
            donate_argnums=(1, 2, 3),
        )
        self._serve_step_paged_fn = jax.jit(
            self._serve_step_paged_program, static_argnames=("steps",),
            donate_argnums=(1, 2, 3),
        )

    def _bucket_args(self):
        """Fresh (bucket state, index) runtime args — None when off."""
        head = self.coded_head
        if head is None or head.executor.buckets is None:
            return None
        return head.executor.bucket_args()

    # ------------------------------------------------------- jit pipeline
    def _can_batch_prefill(self) -> bool:
        """True when ``Model.prefill`` covers this model (same support
        envelope as the slot/paged paths)."""
        c = self.model.config
        return (
            c.family in ("dense", "vlm", "moe")
            and not c.kv_quant
            and c.sliding_window is None
        )

    def _prefill_into_cache(self, params, cache, prompts):
        """Batched prefill spliced into an ``init_cache`` decode state.

        The generate-path counterpart of the serve splice: ONE
        ``Model.prefill`` pass computes every layer's prompt K/V and the
        last-position logits, which land in cache positions
        ``[0, s0)`` / the shared position map. Traceable — used inline by
        ``_gen_program`` and jitted standalone by the legacy host loop.
        """
        b, s0 = prompts.shape
        logits, ks, vs = self.model.prefill(
            params, prompts, jnp.full((b,), s0, jnp.int32)
        )
        kv = cache["kv"]
        cache = {
            **cache,
            "kv": {
                "k": kv["k"].at[:, :, :s0].set(ks),
                "v": kv["v"].at[:, :, :s0].set(vs),
                "pos": kv["pos"].at[:, :s0].set(
                    jnp.arange(s0, dtype=jnp.int32)
                ),
            },
        }
        return logits, cache

    def _coded_select(self, logits, step_key, deadline, true_params=None,
                      bucket_args=None):
        """One coded round on a (B, V) logits batch, fully traceable.

        Pad-vocab sentinels (-1e30) are zeroed before the block mix (they
        would otherwise dominate the float32 solve), decoded logits get
        them re-masked, and the insufficient-survivors fallback is a
        ``jnp.where`` on the decode-ok flag — no shape-dependent Python
        branch inside the compiled program. ``true_params`` optionally
        overrides the straggler-sampling parameters (ground-truth
        injection — see ``set_true_cluster``). ``bucket_args`` — the
        ``(stacked state, index)`` pair from ``executor.bucket_args()`` —
        switches the round onto the bucket-select path: loads, deadline
        and the slot-erasure mask all come from the branch picked
        in-program, so a replan within bucket capacity never retraces
        this program (DESIGN.md §11).

        Returns ``(logits, ok)``: ``ok`` is False on a round whose
        survivors could not decode, where the plain logits stood in.
        """
        head = self.coded_head
        vocab = self.model.config.vocab_size
        ids = jnp.arange(logits.shape[-1])
        lf = logits.astype(jnp.float32)
        clean = jnp.where(ids[None, :] < vocab, lf, 0.0)
        products = head.encode_logits(clean, use_kernel=self.cfg.use_kernel)
        mus, alphas, shifts = (
            true_params if true_params is not None else (None, None, None)
        )
        if bucket_args is not None:
            state, index = bucket_args
            mask, sel = head.executor.finish_mask_bucket_jit(
                step_key, state, index, mus=mus, alphas=alphas, shifts=shifts
            )
            alive = head.executor.slot_mask_bucket_jit(mask, sel)
            dec, ok = head.decode_logits_bucket_jit(products, alive)
        else:
            mask = head.finish_mask_jit(
                step_key, deadline, mus=mus, alphas=alphas, shifts=shifts
            )
            dec, ok = head.decode_logits_jit(products, mask)
        dec = dec[:, : logits.shape[-1]]
        dec = jnp.where(ids[None, :] < vocab, dec, NEG_INF)
        return jnp.where(ok, dec, lf), ok

    def _gen_program(self, params, cache, prompts, key, deadline,
                     true_params=None, bucket_args=None, *, max_new):
        """The whole generation as one traceable program (two lax.scans)."""
        self.traces += 1  # python side effect: runs only while tracing
        b, s0 = prompts.shape
        c = self.model.config
        vp = padded_vocab(c.vocab_size)
        dt = DTYPES_LOGITS[c.logits_dtype]

        if self._can_batch_prefill():
            # one batched forward fills the whole prompt's KV (§4) — the
            # same ``Model.prefill`` splice the serve path uses, so both
            # generation paths share one prefill implementation
            logits, cache = self._prefill_into_cache(params, cache, prompts)
            logits = logits.astype(dt)
        else:
            # sequential fallback for families without a batched
            # cache-returning prefill (hybrid/ssm/audio, kv_quant, ...)
            def prefill_body(carry, inp):
                cache, _ = carry
                tok, pos = inp
                logits, cache = self.model.decode_step(params, cache, tok, pos)
                return (cache, logits), None

            (cache, logits), _ = jax.lax.scan(
                prefill_body,
                (cache, jnp.zeros((b, vp), dt)),
                (prompts.T, jnp.arange(s0, dtype=jnp.int32)),
            )

        def step_logits(logits, step):
            if self.coded_head is None:
                return logits
            return self._coded_select(
                logits, jax.random.fold_in(key, step), deadline, true_params,
                bucket_args,
            )[0]

        # every sampled token goes through the coded head, including the
        # first post-prefill one (the old host loop skipped it)
        tok0 = jnp.argmax(step_logits(logits, 0), -1).astype(jnp.int32)

        def body(carry, t):
            cache, tok = carry
            logits, cache = self.model.decode_step(
                params, cache, tok, s0 + t
            )
            ntok = jnp.argmax(step_logits(logits, t + 1), -1).astype(jnp.int32)
            return (cache, ntok), ntok

        (cache, _), toks = jax.lax.scan(
            body, (cache, tok0), jnp.arange(max_new - 1, dtype=jnp.int32)
        )
        return jnp.concatenate([prompts, tok0[:, None], toks.T], axis=1)

    # ------------------------------------------- continuous batching mode
    def _serve_step_program(self, params, cache, logits, pos, prompts,
                            lengths, row_of_slot, active, key, deadline,
                            true_params=None, bucket_args=None, *, steps):
        """One fused serve iteration: optional admit splice + decode chunk.

        **Admit splice** (``lax.cond``-gated — the batched prefill costs
        nothing on rounds without admissions): ``prompts`` is the
        (S, prompt_cap) right-padded admission batch (row r = r-th
        request placed this round), ``row_of_slot`` maps slot -> admission
        row with −1 for slots keeping their current stream. Every splice
        target is a TRACED argument, so admitting into any slot pattern
        reuses the same compiled program. No token is sampled at admit —
        the prefill logits become the slot's pending-logits state and the
        chunk below samples from them, so every emitted token goes
        through the coded head at the same amortized place and a
        request's ``work`` is exactly 1 prefill round + ``out_len``
        decode rounds.

        **Decode chunk**: ``steps`` slot-decode rounds as one scan; each
        round samples every slot's next token from its pending logits
        (one coded round across the batch) and advances the model one
        step. ``active``: (S,) bool — frozen slots (done or empty)
        rewrite their current KV entry in place (idempotent) and keep
        logits/pos, so the program's shape never depends on which slots
        are live.
        """
        self.serve_traces += 1  # python side effect: runs only while tracing
        row_of_slot = jnp.asarray(row_of_slot, jnp.int32)
        fresh = row_of_slot >= 0  # (S,)

        def splice(ops):
            cache, logits, pos = ops
            plog, ks, vs = self.model.prefill(params, prompts, lengths)
            kv = cache["kv"]
            s_slots, cache_len = kv["pos"].shape
            prompt_cap = prompts.shape[1]
            row = jnp.clip(row_of_slot, 0, None)
            # prefilled K/V land in cache positions [0, prompt_cap) of
            # their slot; the padded tail stays masked via pos = -1
            k_new = jnp.zeros_like(kv["k"]).at[:, :, :prompt_cap].set(
                ks[:, row]
            )
            v_new = jnp.zeros_like(kv["v"]).at[:, :, :prompt_cap].set(
                vs[:, row]
            )
            fkv = fresh[None, :, None, None, None]
            plen = jnp.asarray(lengths, jnp.int32)[row]  # (S,)
            seq = jnp.arange(prompt_cap, dtype=jnp.int32)
            pos_rows = jnp.where(
                seq[None, :] < plen[:, None], seq[None, :], -1
            )
            pos_new = jnp.full((s_slots, cache_len), -1, jnp.int32)
            pos_new = pos_new.at[:, :prompt_cap].set(pos_rows)
            new_cache = {
                "kv": {
                    "k": jnp.where(fkv, k_new, kv["k"]),
                    "v": jnp.where(fkv, v_new, kv["v"]),
                    "pos": jnp.where(fresh[:, None], pos_new, kv["pos"]),
                }
            }
            new_logits = jnp.where(
                fresh[:, None], plog[row].astype(jnp.float32), logits
            )
            new_pos = jnp.where(fresh, plen, pos)
            return new_cache, new_logits, new_pos

        cache, logits, pos = jax.lax.cond(
            jnp.any(fresh), splice, lambda ops: ops,
            (cache, jnp.asarray(logits, jnp.float32),
             jnp.asarray(pos, jnp.int32)),
        )

        def body(carry, t):
            cache, logits, pos = carry
            sel, ok = logits, jnp.bool_(True)
            if self.coded_head is not None:
                sel, ok = self._coded_select(
                    logits, jax.random.fold_in(key, t), deadline, true_params,
                    bucket_args,
                )
            tok = jnp.argmax(sel, -1).astype(jnp.int32)
            nlog, cache = self.model.decode_step_slots(
                params, cache, tok, pos
            )
            logits = jnp.where(
                active[:, None], nlog.astype(jnp.float32), logits
            )
            pos = jnp.where(active, pos + 1, pos)
            return (cache, logits, pos), (tok, ok)

        (cache, logits, pos), (toks, oks) = jax.lax.scan(
            body, (cache, logits, pos), jnp.arange(steps, dtype=jnp.int32)
        )
        return cache, logits, pos, toks, oks

    def _serve_step_paged_program(self, params, cache, logits, pos,
                                  chunk_tokens, chunk_start, chunk_lens,
                                  finishing, tables, active, key, deadline,
                                  true_params=None, bucket_args=None, *,
                                  steps):
        """One fused PAGED serve iteration: prefill chunk + decode chunk.

        The paged twin of ``_serve_step_program`` (DESIGN.md §13). Shapes
        depend only on ``(num_blocks, block_len, S)`` and the prefill
        chunk width — never on any request's prompt length — so admitting
        a 4x-longer prompt retraces nothing: it just runs more admit
        rounds of the SAME program.

        **Prefill chunk** (``lax.cond``-gated): ``chunk_tokens`` is the
        (S, C) batch of this round's prompt chunks, row s covering
        prompt positions ``[chunk_start[s], chunk_start[s] +
        chunk_lens[s])`` of slot s's request (``chunk_lens == 0``: slot
        not prefilling). KV scatters into the slot's pool blocks through
        ``tables``; ``finishing`` marks slots whose prompt COMPLETES
        this round — their last-chunk logits become the slot's pending
        logits and ``pos`` jumps to the prompt length, exactly like the
        dense splice. Mid-prompt chunks update only the pool.

        **Decode chunk**: as in the dense program, but each step runs
        ``decode_step_paged`` — inactive slots (empty / done / still
        prefilling) write to the pool's sink block and keep logits/pos.
        """
        self.serve_traces += 1  # python side effect: runs only while tracing
        chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
        finishing = jnp.asarray(finishing, bool)
        tables = jnp.asarray(tables, jnp.int32)
        active = jnp.asarray(active, bool)

        def splice(ops):
            cache, logits, pos = ops
            plog, new_cache = self.model.prefill_paged(
                params, cache, chunk_tokens, chunk_start, chunk_lens, tables
            )
            new_logits = jnp.where(
                finishing[:, None], plog.astype(jnp.float32), logits
            )
            new_pos = jnp.where(finishing, chunk_start + chunk_lens, pos)
            return new_cache, new_logits, new_pos

        cache, logits, pos = jax.lax.cond(
            jnp.any(chunk_lens > 0), splice, lambda ops: ops,
            (cache, jnp.asarray(logits, jnp.float32),
             jnp.asarray(pos, jnp.int32)),
        )

        def body(carry, t):
            cache, logits, pos = carry
            sel, ok = logits, jnp.bool_(True)
            if self.coded_head is not None:
                sel, ok = self._coded_select(
                    logits, jax.random.fold_in(key, t), deadline, true_params,
                    bucket_args,
                )
            tok = jnp.argmax(sel, -1).astype(jnp.int32)
            nlog, cache = self.model.decode_step_paged(
                params, cache, tok, pos, tables, active,
                use_kernel=False,
            )
            logits = jnp.where(
                active[:, None], nlog.astype(jnp.float32), logits
            )
            pos = jnp.where(active, pos + 1, pos)
            return (cache, logits, pos), (tok, ok)

        (cache, logits, pos), (toks, oks) = jax.lax.scan(
            body, (cache, logits, pos), jnp.arange(steps, dtype=jnp.int32)
        )
        return cache, logits, pos, toks, oks

    def serve(self, trace, *, slots: int = 4, prompt_cap: int | None = None,
              max_out: int | None = None, decode_block: int = 4,
              queue_cap: int = 64, admission_threshold: float = 1.0,
              controller=None, round_latency=None, telemetry=None,
              clock=None, key=None, paged: bool | None = None,
              block_len: int | None = None, num_blocks: int | None = None,
              prefill_chunk: int | None = None,
              tracer=None) -> ServeReport:
        """Continuous batching: serve a request trace through S slots.

        ``trace``: iterable of ``serve.workload.Request`` (arrivals in
        rounds). The scheduler (host) decides placements; the device side
        is ONE fused fixed-shape compiled program per chunk size — a
        ``lax.cond``-gated admit/prefill splice followed by the
        slot-decode chunk — whose arguments carry all per-round
        variation, so admits and evicts never retrace and an admission
        costs no extra dispatch. The program returns nothing the
        scheduler needs, so chunks dispatch asynchronously; the one
        ``block_until_ready`` sits at the end of the run.

        Admission control is wired to ``controller.coverage_latency``
        when an ``AdaptiveController`` is given (or any ``round_latency``
        callable, in round units): the reference latency is sampled once
        at start, and requests are shed when the backlog×slowdown
        projection blows their deadline class's budget
        (``serve.scheduler.SlotScheduler``).

        ``clock`` (a ``runtime.timing.RoundClock``) turns on the
        measured-reality loop (§12): each fused dispatch is timed
        (perf_counter + block_until_ready — chunks no longer overlap,
        that is the price of measuring), decomposed per worker, and —
        when ``controller`` is given — fed to
        ``controller.observe_timing`` so admission control and replans
        run on wall-clock evidence. Requires a coded head.

        ``paged`` (default from ``ServeConfig.paged``) serves from the
        block-pooled KV cache with chunked prefill (DESIGN.md §13):
        ``prompt_cap`` then only sets the default admission chunk width
        (``prefill_chunk``) — prompts longer than the chunk are admitted
        and prefilled across successive admit rounds instead of raising,
        and the cache shape is ``(num_blocks, block_len)``, independent
        of any prompt length. ``num_blocks=None`` sizes the pool so the
        trace can never exhaust it (dense-equivalent capacity);
        an explicit pool turns on memory admission control.
        """
        from repro.serve.scheduler import SlotScheduler

        if clock is not None and self.coded_head is None:
            raise ValueError("clock (measured serving) requires a coded head")

        # span tracing (§14): a telemetry sink implies spans on its
        # stream; an explicit tracer wins; neither means the shared
        # no-op (zero-allocation hot path)
        if tracer is None:
            tracer = (
                SpanTracer(telemetry) if telemetry is not None
                else NULL_TRACER
            )
        self.tracer = tracer
        if self.coded_head is not None:
            self.coded_head.executor.tracer = tracer

        paged = self.cfg.paged if paged is None else paged
        trace = sorted(trace, key=lambda r: (r.arrival, r.rid))
        if not trace:
            raise ValueError("serve needs a non-empty request trace")
        prompt_cap = int(
            prompt_cap if prompt_cap is not None
            else max(r.prompt_len for r in trace)
        )
        if not paged:
            # dense slot caches are (S, prompt_cap + max_out + 1): a
            # longer prompt cannot be represented. Paged mode has no such
            # bound — long prompts prefill chunk-by-chunk instead.
            too_long = [r.rid for r in trace if r.prompt_len > prompt_cap]
            if too_long:
                raise ValueError(
                    f"requests {too_long} exceed prompt_cap={prompt_cap}"
                )
        max_out = int(
            max_out if max_out is not None else max(r.out_len for r in trace)
        )
        # +1: a finished (frozen) slot idempotently rewrites the entry at
        # its final pos, which sits one past its last sampled token
        cache_len = prompt_cap + max_out + 1
        if round_latency is None and controller is not None:
            round_latency = controller.coverage_latency
        reference = 1.0
        if round_latency is not None:
            reference = float(round_latency())
            if not np.isfinite(reference) or reference <= 0:
                reference = 1.0
        if paged:
            return self._serve_paged(
                trace, slots=slots, prompt_cap=prompt_cap, max_out=max_out,
                decode_block=decode_block, queue_cap=queue_cap,
                admission_threshold=admission_threshold,
                controller=controller, round_latency=round_latency,
                reference=reference, telemetry=telemetry, clock=clock,
                key=key, block_len=block_len, num_blocks=num_blocks,
                prefill_chunk=prefill_chunk,
            )
        sched = SlotScheduler(
            slots, queue_cap=queue_cap,
            admission_threshold=admission_threshold,
            round_latency=round_latency, reference_latency=reference,
            telemetry=telemetry, metrics=MetricsRegistry(),
        )
        key = key if key is not None else jax.random.PRNGKey(0)
        deadline = jnp.float32(
            self.coded_head.deadline if self.coded_head is not None else 0.0
        )
        true_params = None
        if self.coded_head is not None:
            true_params = (
                self._true_params
                if self._true_params is not None
                else self.coded_head.executor.worker_params
            )
        bucket_args = self._bucket_args()
        cache = self.model.init_slot_cache(slots, cache_len)
        logits = jnp.zeros((slots, padded_vocab(self.model.config.vocab_size)),
                           jnp.float32)
        pos = jnp.zeros((slots,), jnp.int32)

        now, i, call = 0.0, 0, 0
        prefill_rounds = decode_rounds = 0
        decode_oks = []  # per-dispatch (steps,) coded-decode ok flags
        # constant "no admissions this round" arguments (hoisted so the
        # common no-admit dispatch ships no fresh host arrays)
        no_prompts = jnp.zeros((slots, prompt_cap), jnp.int32)
        no_lengths = jnp.zeros((slots,), jnp.int32)
        no_rows = jnp.full((slots,), -1, jnp.int32)
        t0 = time.perf_counter()
        while i < len(trace) or not sched.idle:
            with tracer.span("admit", round=now) as asp:
                while i < len(trace) and trace[i].arrival <= now + 1e-9:
                    sched.offer(trace[i], now)
                    i += 1
                placed = sched.fill_slots(now)
                asp.set(placed=len(placed))
                if placed:
                    prompts_np = np.zeros((slots, prompt_cap), np.int32)
                    lengths_np = np.zeros((slots,), np.int32)
                    rows_np = np.full((slots,), -1, np.int32)
                    for r, (si, req) in enumerate(placed):
                        prompts_np[r, : req.prompt_len] = req.prompt
                        lengths_np[r] = req.prompt_len
                        rows_np[si] = r
                    prompts = jnp.asarray(prompts_np)
                    lengths = jnp.asarray(lengths_np)
                    rows = jnp.asarray(rows_np)
                else:
                    prompts, lengths, rows = no_prompts, no_lengths, no_rows
            active = [s.busy and not s.done for s in sched.slots]
            if any(active):
                # chunk exactly to the next finish event: slots free the
                # round their stream completes, with zero overshoot (at
                # most ``decode_block`` step-count variants ever compile)
                steps = min(
                    decode_block,
                    min(s.request.out_len - s.generated
                        for s in sched.slots if s.busy and not s.done),
                )
                if clock is not None:
                    # a measured replan may have moved the plan between
                    # dispatches: refresh the per-round runtime args
                    deadline = jnp.float32(self.coded_head.deadline)
                    true_params = (
                        self._true_params
                        if self._true_params is not None
                        else self.coded_head.executor.worker_params
                    )
                    bucket_args = self._bucket_args()
                skey = jax.random.fold_in(key, call)
                with tracer.span("decode_chunk", steps=steps,
                                 round=now, placed=len(placed)):
                    if clock is None:
                        with tracer.span("dispatch"):
                            cache, logits, pos, _, oks = self._serve_step_fn(
                                self.params, cache, logits, pos, prompts,
                                lengths, rows, jnp.asarray(active), skey,
                                deadline, true_params, bucket_args,
                                steps=steps,
                            )
                    else:
                        with tracer.span("dispatch"):
                            timing = clock.measure(
                                lambda: self._serve_step_fn(
                                    self.params, cache, logits, pos,
                                    prompts, lengths, rows,
                                    jnp.asarray(active), skey, deadline,
                                    true_params, bucket_args,
                                    steps=steps,
                                ),
                                key=skey,
                                true_cluster=self._true_cluster,
                            )
                        cache, logits, pos, _, oks = timing.result
                        if controller is not None:
                            d = controller.observe_timing(timing)
                            if (
                                d is not None and d.replanned
                                and self.coded_head
                                    .executor.last_replan_structural
                            ):
                                # next dispatch retraces the re-jitted
                                # program: compile, not round latency
                                clock.discard_next()
                call += 1
                decode_oks.append(oks)
                if placed:  # the fused admit pass costs its own round
                    now += 1.0
                    prefill_rounds += 1
                now += float(steps)
                decode_rounds += steps
                sched.advance(steps)
                sched.retire_done(now)
            elif i < len(trace):
                now = max(now, trace[i].arrival)  # idle: jump to next arrival
            else:
                break
        jax.block_until_ready(logits)
        wall = time.perf_counter() - t0
        report = ServeReport(
            finished=tuple(sched.finished),
            tokens=sum(
                f.tokens for f in sched.finished if f.outcome == "done"
            ),
            rounds=now,
            decode_rounds=decode_rounds,
            prefill_rounds=prefill_rounds,
            admitted=sched.admitted,
            shed=sched.shed,
            wall_s=wall,
            fallback_rounds=_count_fallbacks(decode_oks),
        )
        sched.metrics.emit(telemetry, phase="serve", rounds=float(now))
        return report

    def _serve_paged(self, trace, *, slots, prompt_cap, max_out,
                     decode_block, queue_cap, admission_threshold,
                     controller, round_latency, reference, telemetry, clock,
                     key, block_len, num_blocks, prefill_chunk) -> ServeReport:
        """Paged-KV host loop behind ``serve(paged=True)`` (DESIGN.md §13).

        Differences from the dense loop: physical KV lives in a shared
        ``BlockPool`` (full reservation at admission, freed at
        retirement); prompts prefill in ``chunk``-token pieces across
        admit rounds, so one compiled program per decode-chunk size
        covers EVERY prompt length; and rounds where every busy slot is
        still mid-prompt dispatch a prefill-only pass (``steps=0``).
        """
        from repro.serve.scheduler import BlockPool, SlotScheduler

        chunk = int(prefill_chunk if prefill_chunk is not None
                    else self.cfg.prefill_chunk if self.cfg.prefill_chunk
                    is not None else prompt_cap)
        bl = int(block_len if block_len is not None else self.cfg.block_len)
        nb = num_blocks if num_blocks is not None else self.cfg.num_blocks
        if nb is None:
            # dense-equivalent capacity: every slot can hold the trace's
            # largest request, so the pool never sheds — sizing DOWN from
            # this is the knob that turns on memory admission control
            per_req = max(
                -(-(r.prompt_len + r.out_len + 1) // bl) for r in trace
            )
            nb = slots * per_req
        nb = int(nb)
        cache = self.model.init_paged_cache(nb, bl)
        kv = cache["kv"]
        bytes_per_block = (kv["k"].nbytes + kv["v"].nbytes) // (nb + 1)
        tracer = self.tracer  # resolved by serve()
        # one registry for pool + scheduler: the run snapshots as a unit
        metrics = MetricsRegistry()
        pool = BlockPool(
            nb, bl, bytes_per_block=bytes_per_block, telemetry=telemetry,
            metrics=metrics,
        )
        sched = SlotScheduler(
            slots, queue_cap=queue_cap,
            admission_threshold=admission_threshold,
            round_latency=round_latency, reference_latency=reference,
            telemetry=telemetry, pool=pool, chunk=chunk, metrics=metrics,
        )
        key = key if key is not None else jax.random.PRNGKey(0)
        deadline = jnp.float32(
            self.coded_head.deadline if self.coded_head is not None else 0.0
        )
        true_params = None
        if self.coded_head is not None:
            true_params = (
                self._true_params
                if self._true_params is not None
                else self.coded_head.executor.worker_params
            )
        bucket_args = self._bucket_args()
        logits = jnp.zeros(
            (slots, padded_vocab(self.model.config.vocab_size)), jnp.float32
        )
        pos = jnp.zeros((slots,), jnp.int32)
        # host mirror of the device block tables, width = pool size (a
        # slot can never hold more than every block): shapes depend only
        # on (num_blocks, block_len, S)
        table_np = np.full((slots, nb), -1, np.int32)
        no_chunk = jnp.zeros((slots, chunk), jnp.int32)
        no_i32 = jnp.zeros((slots,), jnp.int32)
        no_bool = jnp.zeros((slots,), bool)

        now, i, call = 0.0, 0, 0
        prefill_rounds = decode_rounds = 0
        decode_oks = []  # per-dispatch (steps,) coded-decode ok flags
        t0 = time.perf_counter()
        while i < len(trace) or not sched.idle:
            with tracer.span("admit", round=now) as asp:
                while i < len(trace) and trace[i].arrival <= now + 1e-9:
                    sched.offer(trace[i], now)
                    i += 1
                placed = sched.fill_slots(now)
                asp.set(placed=len(placed))
                for si, _req in placed:
                    blocks = sched.slots[si].blocks
                    table_np[si, :] = -1
                    table_np[si, : len(blocks)] = blocks
            # this round's prefill chunk: the next `chunk` unconsumed
            # prompt tokens of EVERY slot still mid-prompt (fresh admits
            # included) — one batched pass covers them all
            chunk_np = start_np = lens_np = fin_np = None
            notes = []
            for si, s in enumerate(sched.slots):
                if not s.prefilling:
                    continue
                if chunk_np is None:
                    chunk_np = np.zeros((slots, chunk), np.int32)
                    start_np = np.zeros((slots,), np.int32)
                    lens_np = np.zeros((slots,), np.int32)
                    fin_np = np.zeros((slots,), bool)
                take = min(chunk, s.request.prompt_len - s.prefilled)
                chunk_np[si, :take] = s.request.prompt[
                    s.prefilled : s.prefilled + take
                ]
                start_np[si] = s.prefilled
                lens_np[si] = take
                fin_np[si] = s.prefilled + take >= s.request.prompt_len
                notes.append((si, take))
            prefilling = chunk_np is not None
            # decode-eligible AFTER the splice: done prefilling already,
            # or finishing it in this very dispatch (so a short prompt
            # still costs exactly 1 admit round + out_len decode rounds,
            # matching the dense path's accounting)
            active = [
                s.busy and not s.done
                and (not s.prefilling or (fin_np is not None and fin_np[si]))
                for si, s in enumerate(sched.slots)
            ]
            steps = 0
            if any(active):
                steps = min(
                    decode_block,
                    min(s.request.out_len - s.generated
                        for si, s in enumerate(sched.slots) if active[si]),
                )
            if prefilling or steps > 0:
                if clock is not None:
                    deadline = jnp.float32(self.coded_head.deadline)
                    true_params = (
                        self._true_params
                        if self._true_params is not None
                        else self.coded_head.executor.worker_params
                    )
                    bucket_args = self._bucket_args()
                skey = jax.random.fold_in(key, call)
                args = (
                    self.params, cache, logits, pos,
                    jnp.asarray(chunk_np) if prefilling else no_chunk,
                    jnp.asarray(start_np) if prefilling else no_i32,
                    jnp.asarray(lens_np) if prefilling else no_i32,
                    jnp.asarray(fin_np) if prefilling else no_bool,
                    jnp.asarray(table_np), jnp.asarray(active), skey,
                    deadline, true_params, bucket_args,
                )
                # a round that splices prompt chunks is a prefill round
                # even when finishing slots decode in the same dispatch
                with tracer.span(
                    "prefill_chunk" if prefilling else "decode_chunk",
                    steps=steps, round=now, placed=len(placed),
                ):
                    if clock is None:
                        with tracer.span("dispatch"):
                            cache, logits, pos, _, oks = (
                                self._serve_step_paged_fn(
                                    *args, steps=steps
                                )
                            )
                    else:
                        with tracer.span("dispatch"):
                            timing = clock.measure(
                                lambda: self._serve_step_paged_fn(
                                    *args, steps=steps
                                ),
                                key=skey, true_cluster=self._true_cluster,
                            )
                        cache, logits, pos, _, oks = timing.result
                        if controller is not None:
                            d = controller.observe_timing(timing)
                            if (
                                d is not None and d.replanned
                                and self.coded_head
                                    .executor.last_replan_structural
                            ):
                                clock.discard_next()
                call += 1
                decode_oks.append(oks)
                for si, take in notes:
                    sched.note_prefill(si, take)
                if prefilling:  # the batched chunk pass costs one round
                    now += 1.0
                    prefill_rounds += 1
                if steps > 0:
                    now += float(steps)
                    decode_rounds += steps
                    sched.advance(steps)
                for si, _fin in sched.retire_done(now):
                    table_np[si, :] = -1
            elif i < len(trace):
                now = max(now, trace[i].arrival)  # idle: jump to next arrival
            else:
                break
        jax.block_until_ready(logits)
        wall = time.perf_counter() - t0
        report = ServeReport(
            finished=tuple(sched.finished),
            tokens=sum(
                f.tokens for f in sched.finished if f.outcome == "done"
            ),
            rounds=now,
            decode_rounds=decode_rounds,
            prefill_rounds=prefill_rounds,
            admitted=sched.admitted,
            shed=sched.shed,
            wall_s=wall,
            fallback_rounds=_count_fallbacks(decode_oks),
        )
        metrics.emit(telemetry, phase="serve", rounds=float(now))
        return report

    # ------------------------------------------------------------ public
    def generate(self, prompts, max_new: int | None = None, *, key=None,
                 cache_len: int | None = None, extras=None):
        """Greedy decode. prompts: (B, S0) int32. Returns (B, S0+T)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        max_new = int(self.cfg.max_decode_steps if max_new is None else max_new)
        if max_new == 0:
            return jnp.asarray(prompts, jnp.int32)
        b, s0 = prompts.shape
        cache_len = cache_len or (s0 + max_new)
        cache = self.model.init_cache(b, cache_len, extras)
        if not self.cfg.jit_pipeline:
            return self._generate_hostloop(prompts, max_new, key, cache)
        deadline = jnp.float32(
            self.coded_head.deadline if self.coded_head is not None else 0.0
        )
        # straggler-sampling parameters ride along as (W,) arrays so the
        # scenario layer can change the truth every round without a
        # retrace (shapes only change on replan, which re-jits anyway)
        true_params = None
        if self.coded_head is not None:
            true_params = (
                self._true_params
                if self._true_params is not None
                else self.coded_head.executor.worker_params
            )
        with self.tracer.span("dispatch", kind="generate",
                              max_new=max_new, batch=b):
            return self._generate_fn(
                self.params, cache, jnp.asarray(prompts, jnp.int32), key,
                deadline, true_params, self._bucket_args(), max_new=max_new,
            )

    # ------------------------------------------------- legacy host loop
    def _generate_hostloop(self, prompts, max_new, key, cache):
        """Per-token Python loop with numpy decode (reference/baseline).

        Kept for ``benchmarks/serve_throughput.py``: this is the path the
        jit pipeline replaces — one host round-trip per decoded token.
        Prefill routes through the same jitted ``Model.prefill`` splice
        as the compiled pipeline (one shared prefill implementation)
        where supported; only the token loop stays sequential.
        """
        b, s0 = prompts.shape
        prompts = jnp.asarray(prompts, jnp.int32)
        if self._can_batch_prefill():
            logits, cache = self._prefill_fn(self.params, cache, prompts)
        else:
            logits = None
            for pos in range(s0):
                logits, cache = self._decode(
                    self.params, cache, prompts[:, pos], jnp.int32(pos)
                )
        out = [prompts]
        if self.coded_head is not None:
            logits = self._coded_logits(logits, key, 0)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for t in range(max_new):
            out.append(tok[:, None])
            if t == max_new - 1:
                break
            logits, cache = self._decode(self.params, cache, tok, jnp.int32(s0 + t))
            if self.coded_head is not None:
                logits = self._coded_logits(logits, key, t + 1)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return jnp.concatenate(out, axis=1)

    def _coded_logits(self, fallback_logits, key, step):
        """Recompute the final logits through the coded LM head (host path)."""
        head = self.coded_head
        vocab = self.model.config.vocab_size
        with self.tracer.span("erasure_solve", step=step) as sp:
            ids = np.arange(fallback_logits.shape[-1])
            lf = np.asarray(fallback_logits, np.float32)
            clean = np.where(ids[None, :] < vocab, lf, 0.0)
            products = head.encode_logits(
                jnp.asarray(clean), use_kernel=self.cfg.use_kernel
            )
            mask = head.sample_finish_mask(jax.random.fold_in(key, step))
            logits, ok = head.decode_logits(products, mask)
            sp.set(ok=bool(ok))
            if not ok:  # insufficient survivors: fall back (a real
                return fallback_logits  # system would extend the deadline)
            logits = logits[:, : fallback_logits.shape[-1]]
            logits = np.where(ids[None, :] < vocab, logits, NEG_INF)
            return jnp.asarray(logits)
