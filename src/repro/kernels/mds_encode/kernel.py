"""Pallas TPU kernel: one-time MDS encode ``A~ = G A``.

The paper's setup phase — encoding the data matrix with the (n, k)
generator — is a dense matmul. MXU-native tiling:

* grid = (n/BN, d/BD, k/BK); each step multiplies a (BN, BK) G tile by a
  (BK, BD) A tile on the MXU (all dims multiples of 128) and accumulates
  into a (BN, BD) f32 VMEM scratch.
* BN = BD = BK = 256: three tiles of 256x256 bf16 (128 KiB each) plus
  the f32 accumulator (256 KiB) stay far under VMEM with double
  buffering; 256 keeps MXU (128x128 systolic) fully fed with 2x2 passes.
* k-accumulation uses the revisiting-output pattern (zero at kk == 0,
  flush at kk == last).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BN = 256
BD = 256
BK = 256


def _kernel(g_ref, a_ref, o_ref, acc_ref):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        g_ref[...].astype(jnp.float32),
        a_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bd", "bk", "interpret"))
def encode_kernel(g, a, *, bn: int = BN, bd: int = BD, bk: int = BK,
                  interpret: bool = False):
    n, k = g.shape
    k2, d = a.shape
    assert k == k2 and n % bn == 0 and d % bd == 0 and k % bk == 0
    grid = (n // bn, d // bd, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bd), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bd), jnp.float32)],
        interpret=interpret,
    )(g, a)
