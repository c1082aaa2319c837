"""Coded LM-head serving under injected stragglers.

    PYTHONPATH=src python examples/coded_serving.py

Serves batched greedy decoding from a small dense LM where the final
unembedding matvec — exactly the paper's workload shape — runs through
an (n, k) MDS code over a heterogeneous simulated fleet. Workers that
miss the deadline (T* x safety factor, from the paper's Theorem 2) are
erasures; logits are recovered from any k surviving coded blocks. The
demo verifies coded output == uncoded output even with stragglers.

The whole generation — prefill, straggler-mask sampling, erasure decode,
fallback — is ONE compiled program (jax.lax.scan; see DESIGN.md §4).
"""
import time
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core.runtime_model import ClusterSpec
from repro.models.model import Model
from repro.runtime.serve_loop import CodedLMHead, ServeConfig, Server

config = get_arch("qwen3-0.6b").reduced()
model = Model(config)
params = model.init_params(jax.random.PRNGKey(0))

# 12 workers in two speed groups; the slow group straggles hard.
fleet = ClusterSpec.make([6, 6], [8.0, 0.7])
server = Server(model, params, fleet, ServeConfig(block_rows=64))
head: CodedLMHead = server.coded_head
print(f"coded LM head: V={config.vocab_size} -> kb={head.kb} blocks, "
      f"(n,k)=({head.nb},{head.kb}) rate={head.kb / head.nb:.3f}")
print(f"per-worker block loads (Theorem 2): "
      f"{head.plan.loads_per_worker.tolist()}")
print(f"deadline = T* x 3 = {head.deadline:.4f}")

# how often does the fleet miss (insufficient survivors)?
misses, trials = 0, 200
for t in range(trials):
    mask = head.sample_finish_mask(jax.random.PRNGKey(t))
    blocks = sum(
        int(head.plan.loads_per_worker[w]) for w in np.flatnonzero(mask)
    )
    misses += blocks < head.kb
print(f"decode-failure rate at this deadline: {misses / trials:.1%}")

max_new = 12
prompts = jax.random.randint(
    jax.random.PRNGKey(7), (4, 8), 0, config.vocab_size
).astype(jnp.int32)
out_coded = server.generate(prompts, max_new=max_new)  # compiles once...
t0 = time.perf_counter()
out_coded = server.generate(prompts, max_new=max_new)
dt = time.perf_counter() - t0
print(f"jit pipeline: {prompts.shape[0] * max_new / dt:.1f} tok/s "
      f"({server.traces} trace(s) across 2 generate calls)")
plain = Server(model, params, None, ServeConfig())
out_plain = plain.generate(prompts, max_new=max_new)
match = bool(jnp.all(out_coded == out_plain))
print(f"coded == uncoded greedy outputs: {match}")
print("sample continuation:", out_coded[0, 8:].tolist())
