"""Compile a cell's serve programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/aot.py --workload <cell>

Builds the cell's ``Server`` around shapes, not weights (only the coded
head's float32 table is made, in zeros, because the head reads its
shape), and compiles the paged serve program for every chunk size the
traffic can dispatch (0 to ``decode_block`` decode steps) on one chip of
a described ``v5e:2x2``. Prints each program's ``memory_analysis()``.
What the chip's compiler refuses shows here at no chip time. It runs
nothing, so it gives no time.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def compile_cell(name: str, log=print) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run as R
    from repro.core.runtime_model import ClusterSpec
    from repro.models.model import Model, padded_vocab
    from repro.runtime.serve_loop import ServeConfig, Server

    _, cell, cfg, mix, _ = R.load_cell(name)
    mc = R.program_config(cfg)
    model = Model(mc)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    params = sds(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    vp = padded_vocab(mc.vocab_size)
    table = np.zeros((vp, mc.d_model), np.float32)
    sysc = cfg["system"]
    server = Server(model, {"embed": {"table": table}},
                    ClusterSpec.parse(sysc["fleet"]), ServeConfig(
                        block_rows=sysc["block_rows"],
                        deadline_safety=sysc["deadline_safety"],
                        scheme=sysc["scheme"], block_len=mix["block_len"],
                        num_blocks=mix["num_blocks"],
                        prefill_chunk=mix["prefill_chunk"]))
    head = server.coded_head
    s, nb, c = mix["slots"], mix["num_blocks"], mix["prefill_chunk"]
    i32, b = jnp.int32, jnp.bool_
    args = (
        params,
        sds(jax.eval_shape(lambda: model.init_paged_cache(nb, mix["block_len"]))),
        jax.ShapeDtypeStruct((s, vp), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((s,), i32, sharding=one),
        jax.ShapeDtypeStruct((s, c), i32, sharding=one),
        jax.ShapeDtypeStruct((s,), i32, sharding=one),
        jax.ShapeDtypeStruct((s,), i32, sharding=one),
        jax.ShapeDtypeStruct((s,), b, sharding=one),
        jax.ShapeDtypeStruct((s, nb), i32, sharding=one),
        jax.ShapeDtypeStruct((s,), b, sharding=one),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one),
        sds(jax.tree.map(jnp.asarray, head.executor.worker_params)),
    )
    log(f"{name}: coded head (nb, kb)=({head.nb}, {head.kb}); pool {nb} blocks "
        f"of {mix['block_len']}; {s} slots; prefill chunk {c}")
    out = {}
    for steps in range(mix["decode_block"] + 1):
        compiled = server._serve_step_paged_fn.lower(*args, steps=steps).compile()
        mem = compiled.memory_analysis()
        out[steps] = {k: getattr(mem, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes", "generated_code_size_in_bytes")}
        log(f"  steps={steps}: {out[steps]}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    compile_cell(args.workload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
