"""Pallas TPU kernel: per-worker coded subtask ``y = A~_i x``.

TPU adaptation of the paper's worker computation (a plain matvec on the
paper's CPU workers). The kernel takes a BATCH of vectors as the rows of
``x`` and returns ``x A^T``: one vector is the paper's matvec, several
(the decode batch, or the B x R logit columns of the coded head's block
mix) share every A tile they stream through. Tiling targets the v5e
memory hierarchy:

* grid = (N/BN, R/BR, D/BD); each step loads an A tile (BR, BD) and an x
  tile (BN, BD) HBM->VMEM and accumulates a (BN, BR) f32 partial on the
  MXU (an NT contraction over BD).
* Every block is 2-D with its last two dims (8, 128)-aligned: Mosaic
  refuses 1-D vector blocks (their XLA layout tiles by 1024, Mosaic's by
  256), so a single vector rides as an 8-row x tile.
* BR = 256 rows, BD = 1024 columns: an f32 A tile is 1 MiB, well under
  the ~16 MiB scoped VMEM budget with double buffering.
* Accumulation across the D-grid dimension uses the standard
  revisiting-output pattern: zero the accumulator when j == 0, add every
  step, store at the last j.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BN = 256  # vectors per tile (a lone vector pads to 8)
BR = 256  # rows of A per tile
BD = 1024  # d-columns per tile


def _kernel(x_ref, a_ref, o_ref, acc_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), a_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (BN, BR)

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "br", "bd", "interpret"))
def matvec_kernel(a, x, *, bn: int = BN, br: int = BR, bd: int = BD,
                  interpret: bool = False):
    """x A^T: a (R, D), x (N, D) -> (N, R). Shapes must be multiples of
    the tiles — ops.py pads."""
    r, d = a.shape
    n, d2 = x.shape
    assert d == d2 and n % bn == 0 and r % br == 0 and d % bd == 0, (
        a.shape, x.shape, bn, br, bd,
    )
    return pl.pallas_call(
        _kernel,
        grid=(n // bn, r // br, d // bd),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, k, j: (i, j)),
            pl.BlockSpec((br, bd), lambda i, k, j: (k, j)),
        ],
        out_specs=pl.BlockSpec((bn, br), lambda i, k, j: (i, k)),
        out_shape=jax.ShapeDtypeStruct((n, r), a.dtype),
        scratch_shapes=[pltpu.VMEM((bn, br), jnp.float32)],
        interpret=interpret,
    )(x, a)
