"""Pure-jnp oracle for the coded-matvec kernel."""
import jax
import jax.numpy as jnp


def matvec_ref(a, x):
    """A x with f32 accumulation. a: (R, D); x: (D,) -> (R,) or rows of
    vectors (N, D) -> (N, R)."""
    return jnp.matmul(
        x.astype(jnp.float32), a.astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(a.dtype)


def matvec_batch_ref(a, x):
    """a: (W, L, ..., D); x: (D,) -> (W, L, ...)."""
    return jnp.einsum(
        "...d,d->...", a.astype(jnp.float32), x.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ).astype(a.dtype)
