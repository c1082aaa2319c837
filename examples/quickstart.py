"""Quickstart: the paper's optimal load allocation in five minutes.

    PYTHONPATH=src python examples/quickstart.py

1. Define a heterogeneous cluster (groups of workers with different
   straggling parameters mu and shifts alpha).
2. Compute the paper's optimal allocation (Theorem 2) and the optimal
   (n*, k) MDS code.
3. Monte-Carlo the actual latency and compare with the lower bound T*
   and with the uniform baseline.
4. Run one real coded matvec end-to-end (encode -> distribute ->
   compute with the Pallas kernel -> straggler erasure -> decode).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allocation import optimal_allocation, uniform_given_n
from repro.core.coded_matvec import end_to_end_coded_matvec
from repro.core.planner import plan_deployment
from repro.core.runtime_model import ClusterSpec
from repro.core.simulator import expected_latency

# ---------------------------------------------------------------- step 1
# Three groups: 40 fast, 60 medium, 100 slow workers.
cluster = ClusterSpec.make(
    num_workers=[40, 60, 100], mus=[8.0, 2.0, 0.5], alphas=1.0
)
k = 20_000  # rows of the data matrix A

# ---------------------------------------------------------------- step 2
plan = optimal_allocation(cluster, k)
print("optimal per-group loads l*_j:", np.round(plan.loads, 1).tolist())
print(f"optimal (n*, k) MDS code: n* = {plan.n:.0f}, rate = {plan.rate:.3f}")
print(f"lower-bound expected latency T* = {plan.t_star:.5f}")

# ---------------------------------------------------------------- step 3
key = jax.random.PRNGKey(0)
mc = expected_latency(key, cluster, plan, num_trials=8_000)
uni = expected_latency(
    key, cluster, uniform_given_n(cluster, k, plan.n), num_trials=8_000
)
print(f"Monte-Carlo latency (proposed): {mc:.5f}  ({mc / plan.t_star:.3f} x T*)")
print(f"Monte-Carlo latency (uniform, same code): {uni:.5f} "
      f"({100 * (1 - mc / uni):.1f}% slower than proposed)")

# ---------------------------------------------------------------- step 4
small = ClusterSpec.make([4, 4], [4.0, 1.0])
dep = plan_deployment(small, k=96)
a = jax.random.normal(key, (96, 128))
x = jax.random.normal(jax.random.fold_in(key, 1), (128,))
mesh = jax.make_mesh((len(jax.devices()),), ("workers",))
finished = np.ones(dep.num_workers, dtype=bool)
finished[-2:] = False  # two slow-group stragglers miss the deadline
# the Pallas kernel runs compiled, so it needs a TPU; elsewhere the
# worker matvec is the einsum route
on_tpu = jax.default_backend() == "tpu"
y, ok = end_to_end_coded_matvec(mesh, a, x, dep, finished, use_kernel=on_tpu)
err = float(jnp.max(jnp.abs(jnp.asarray(y) - a @ x)))
print(f"coded matvec with 2 erasures: recovered={ok}, max|err|={err:.2e}")
