"""Distributed coded matvec: the paper's master/worker pattern on a mesh.

TPU adaptation (see DESIGN.md §3): SPMD collectives cannot early-exit on
"first k rows", so the runtime path is deadline-based — every worker
computes its block, an erasure mask marks which workers met the deadline
(injected by tests; produced by the telemetry layer in deployment), and
the master decodes A·x from the surviving coded rows.

Layout: the coded matrix ``A~`` is laid out worker-major with per-worker
blocks PADDED to ``max_load`` rows so the array shards evenly over the
``workers`` mesh axis: shape (W, max_load, d), or (W, max_load, R, d)
when the code runs over blocks of R rows (the coded LM head's layout).
shard_map gives each device its workers' blocks; the local product is
one matvec (the Pallas kernel in
``repro/kernels/coded_matvec`` is the TPU-tiled version, selectable with
``use_kernel=True``); results are all-gathered and decoded.

The hot path is ``DecodePipeline``: matvec, erasure-mask application and
the fixed-shape decode fused into ONE jitted master step, so a coded
round never round-trips through the host (DESIGN.md §4). The split
``coded_matvec`` / ``decode_coded_result`` pair remains as the host-side
reference path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.coding import (
    decode_from_rows,
    decode_systematic_jit,
    encode,
    make_generator,
)
from repro.core.planner import DeploymentPlan


def pack_coded_matrix(generator, a, plan: DeploymentPlan):
    """Encode A and pack per-worker blocks padded to max_load, on device.

    ``a`` is (k, d), or (k, R, d) when the code runs over row BLOCKS of R
    rows (the coded LM head's layout): a coded row is then a whole
    (R, d) block and a worker's product for it is an (R,) vector.

    Returns:
      packed: (W, max_load, ..., d) — worker i's coded rows in
        [i, :load_i], zeros in the pad slots.
      row_of: (W, max_load) int32 numpy — index into coded rows for each
        packed slot (used to select generator rows at decode time); -1 = pad.
    """
    coded = encode(generator, jnp.asarray(a, jnp.float32))
    row_of = np.full((plan.num_workers, plan.max_load), -1, dtype=np.int32)
    for i, (s, e) in enumerate(plan.row_ranges):
        row_of[i, : e - s] = np.arange(s, e, dtype=np.int32)
    pad = (row_of < 0).reshape(row_of.shape + (1,) * (coded.ndim - 1))
    packed = jnp.where(pad, 0.0, coded[np.maximum(row_of, 0)])
    return packed, row_of


def _local_matvec(a_block, x):
    # a_block: (W_local, max_load, ..., d) on this shard; x replicated (d,).
    # Full float32: the decode solve amplifies bfloat16-rounded products.
    return jnp.einsum(
        "wl...d,d->wl...", a_block, x, precision=jax.lax.Precision.HIGHEST
    )


def coded_matvec(
    mesh: Mesh,
    packed,
    x,
    *,
    axis: str = "workers",
    use_kernel: bool = False,
):
    """All-workers coded product: (W, max_load) of A~_i x, sharded on axis.

    This is the hot path (the paper's per-worker subtask). Decode is
    separate (`decode_coded_result`) because the erasure mask is only
    known at the deadline.
    """
    if use_kernel:
        from repro.kernels.coded_matvec import ops as cmv_ops

        local = lambda a_block, xv: cmv_ops.blocked_matvec_batch(a_block, xv)
    else:
        local = _local_matvec

    fn = jax.jit(
        jax.shard_map(
            lambda a_block, xv: local(a_block, xv),
            mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(axis),
            # pallas_call outputs carry no varying-mesh-axes metadata
            check_vma=False,
        )
    )
    return fn(packed, x)


def decode_coded_result(
    generator, row_of, partials, finished_workers, k: int
):
    """Master-side decode from the workers that met the deadline.

    Args:
      generator: (n, k) MDS generator used at pack time.
      row_of: (W, max_load) packed-slot -> coded-row map (-1 pads).
      partials: (W, max_load) per-slot inner products.
      finished_workers: (W,) bool mask.
      k: uncoded rows.

    Returns (z, ok): least-squares recovery of A x.
    """
    row_of = np.asarray(row_of)
    partials = np.asarray(partials)
    fin = np.asarray(finished_workers)
    slot_ok = (row_of >= 0) & fin[:, None]
    rows = row_of[slot_ok]
    vals = partials[slot_ok]
    if rows.size < k:
        return np.zeros((k,), dtype=partials.dtype), False
    g_rows = np.asarray(generator)[rows]
    z = np.asarray(decode_from_rows(jnp.asarray(g_rows), jnp.asarray(vals)))
    return z, True


def masked_decode(generator, row_of, partials, finished_workers):
    """Fuse erasure-mask application + decode, entirely on-device.

    Scatters the packed per-slot products into coded-row order (pad slots
    and straggler workers dropped via out-of-bounds indices), marks the
    surviving rows, and runs the fixed-shape jit decode. Traceable — the
    jitted master step of ``DecodePipeline`` inlines it after the
    shard_map matvec so compute -> mask -> decode is one XLA program.

    Returns (z, ok) with ``ok`` a traced bool (False: < k rows survived).
    """
    generator = jnp.asarray(generator)
    n = generator.shape[0]
    row_of = jnp.asarray(row_of)
    partials = jnp.asarray(partials)
    fin = jnp.asarray(finished_workers, dtype=bool)
    # row index per packed slot; dead/pad slots pushed out of bounds
    rows = jnp.where((row_of >= 0) & fin[:, None], row_of, n).ravel()
    block = partials.shape[row_of.ndim:]  # (R,) per slot for block codes
    y = jnp.zeros((n, *block), partials.dtype).at[rows].set(
        partials.reshape(rows.shape[0], *block), mode="drop"
    )
    alive = jnp.zeros((n,), bool).at[rows].set(True, mode="drop")
    return decode_systematic_jit(generator, y, alive)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "use_kernel")
)
def _fused_master_step(
    packed, x, finished_workers, generator, row_of, *, mesh, axis, use_kernel
):
    """One compiled coded round: sharded matvec -> mask -> decode.

    Module-level so the jit cache is shared across ``DecodePipeline``
    instances (Mesh objects are hashable): repeated pipelines over the
    same deployment shapes reuse one compiled program.
    """
    if use_kernel:
        from repro.kernels.coded_matvec import ops as cmv_ops

        local = cmv_ops.blocked_matvec_batch
    else:
        local = _local_matvec
    sharded = jax.shard_map(
        lambda a_block, xv: local(a_block, xv),
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    partials = sharded(packed, x)
    return masked_decode(generator, row_of, partials, finished_workers)


class DecodePipeline:
    """Jit-native master step: matvec -> erasure mask -> decode, one jit.

    Binds the deployment state (mesh, generator, slot->row map, kernel
    choice) at construction; each call runs the whole coded round as a
    single compiled program with no host transfer between the
    distributed compute and the decode (see DESIGN.md §4).
    """

    def __init__(self, mesh: Mesh, generator, row_of, *,
                 axis: str = "workers", use_kernel: bool = False):
        self.mesh = mesh
        self.axis = axis
        self.use_kernel = use_kernel
        self.generator = jnp.asarray(generator)
        self.row_of = jnp.asarray(row_of)

    def __call__(self, packed, x, finished_workers):
        return _fused_master_step(
            packed, x, finished_workers, self.generator, self.row_of,
            mesh=self.mesh, axis=self.axis, use_kernel=self.use_kernel,
        )


def end_to_end_coded_matvec(
    mesh: Mesh,
    a,
    x,
    plan: DeploymentPlan,
    finished_workers=None,
    *,
    key=None,
    use_kernel: bool = False,
    jit_decode: bool = True,
):
    """Convenience wrapper: encode -> distribute -> compute -> decode.

    ``jit_decode=True`` (default) runs the fused ``DecodePipeline`` —
    the result never leaves the device between compute and decode.
    ``jit_decode=False`` keeps the legacy host-side numpy decode as a
    reference path.
    """
    k = a.shape[0]
    assert k == plan.k
    gen = make_generator(plan.n, k, key=key)
    packed, row_of = pack_coded_matrix(gen, a, plan)
    if finished_workers is None:
        finished_workers = np.ones((plan.num_workers,), dtype=bool)
    if jit_decode:
        pipeline = DecodePipeline(mesh, gen, row_of, use_kernel=use_kernel)
        return pipeline(
            jnp.asarray(packed), jnp.asarray(x), jnp.asarray(finished_workers)
        )
    partials = coded_matvec(mesh, jnp.asarray(packed), jnp.asarray(x),
                            use_kernel=use_kernel)
    return decode_coded_result(gen, row_of, partials, finished_workers, k)
