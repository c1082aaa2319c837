"""The serve loop's tracing (DESIGN.md §14): named scopes on the compiled
paged program, host spans that tile ``Server.serve``, spans on a
``jax.profiler`` trace's clock, and the repairs inside the new spans."""
import glob
import json
import re

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import ClusterSpec
from repro.launch import serve as serve_cli
from repro.models.model import Model
from repro.obs.trace import (
    LAYER_SCOPES,
    NULL_TRACER,
    SCOPE_MLP,
    SCOPE_MOE,
    SCOPE_WINDOW_ATTENTION,
    SCOPES,
    SpanTracer,
)
from repro.runtime.serve_loop import ServeConfig, Server
from repro.serve import Request, make_workload

KEY = jax.random.PRNGKey(0)
#: one letter per top-level span, for matching the order of a serve call
LETTER = {"serve_setup": "S", "admit": "A", "prepare": "P",
          "prefill_chunk": "C", "decode_chunk": "C", "retire": "R",
          "finish": "F"}


@pytest.fixture(scope="module")
def server():
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    return Server(m, m.init_params(KEY), ClusterSpec.make([2, 2], [4.0, 0.8]),
                  ServeConfig(block_rows=64))


def _trace(vocab, out_lens, plen=5):
    return [Request(rid=i, arrival=0.0,
                    prompt=tuple((7 * i + j) % vocab + 1 for j in range(plen)),
                    out_len=n, deadline_class="standard")
            for i, n in enumerate(out_lens)]


class _Events:
    """Counts JAX's compile-pipeline events while open."""

    NAMES = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_kw):
        if event in self.NAMES:
            self.n += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


@pytest.fixture(scope="module")
def expert_server():
    """Window and full layers, routed experts, an untied head."""
    c = ARCHS["mellum2-12b-a2.5b"].reduced()
    m = Model(c)
    return Server(m, m.init_params(KEY), ClusterSpec.make([2, 2], [4.0, 0.8]),
                  ServeConfig(block_rows=64))


#: the scopes each served model's blocks leave out
ABSENT = {"server": {SCOPE_WINDOW_ATTENTION, SCOPE_MOE},
          "expert_server": {SCOPE_MLP}}


@pytest.mark.parametrize("which", sorted(ABSENT))
def test_every_scope_names_ops_of_the_compiled_paged_program(which, request):
    server = request.getfixturevalue(which)
    vocab = server.model.config.vocab_size
    trace = _trace(vocab, [3, 5, 2, 4])
    untraced = server.serve(trace, slots=2, decode_block=2)
    assert untraced.scopes is None  # nothing is read with tracing off
    traces = server.serve_traces
    with _Events() as ev:
        rep = server.serve(trace, slots=2, decode_block=2, tracer=SpanTracer())
    # reading the programs' text lowers and compiles nothing new
    assert ev.n == 0 and server.serve_traces == traces
    assert rep.scopes and all(rep.scopes.values())
    named = set().union(*(m.values() for m in rep.scopes.values()))
    assert named == set(SCOPES + LAYER_SCOPES) - ABSENT[which]
    # instruction names as a device profile gives them
    assert all(k.startswith("%") for m in rep.scopes.values() for k in m)


def test_traced_serve_spans_tile_the_call_in_order(server):
    vocab = server.model.config.vocab_size
    tracer = SpanTracer()
    rep = server.serve(_trace(vocab, [3, 6, 2, 5, 4]), slots=2,
                       decode_block=2, tracer=tracer)
    spans = sorted(tracer.spans, key=lambda s: s.t0_s)
    top = [s for s in spans if s.depth == 0]
    order = "".join(LETTER[s.name] for s in top)
    assert re.fullmatch(r"S(AP(CR)?)+F", order), order
    # each chunk span holds exactly its one dispatch
    inner = [s for s in spans if s.depth > 0]
    assert [s.name for s in inner] == ["dispatch"] * order.count("C")
    assert all(s.parent in ("prefill_chunk", "decode_chunk") for s in inner)
    # top-level spans follow one another without overlap
    assert all(a.t0_s + a.dur_s <= b.t0_s for a, b in zip(top, top[1:]))
    chunks = [s for s in top if LETTER[s.name] == "C"]
    assert sum(s.attrs["steps"] for s in chunks) == rep.decode_rounds
    assert all(s.attrs["host_bytes"] > 0 for s in top if s.name == "prepare"
               and s.attrs)


def test_annotating_tracer_lands_spans_in_the_profile_host_plane(tmp_path):
    from jax.profiler import ProfileData

    tracer = SpanTracer(annotate=True)
    with jax.profiler.trace(str(tmp_path)):
        for name in ("admit", "prepare"):
            with tracer.span(name):
                pass
        with tracer.span("decode_chunk"):
            with tracer.span("dispatch"):
                jax.block_until_ready(jax.numpy.ones(3) + 1)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    names = {e.name for p in host for line in p.lines for e in line.events}
    assert {"admit", "prepare", "decode_chunk", "dispatch"} <= names
    assert [s.name for s in tracer.spans] == [
        "admit", "prepare", "dispatch", "decode_chunk"]


def test_serve_cli_profile_holds_spans_and_scope_maps(tmp_path, capsys):
    from jax.profiler import ProfileData

    out = tmp_path / "prof"
    serve_cli.main(["--arch", "qwen3-0.6b", "--reduced", "--coded",
                    "--trace", "chat", "--num-requests", "3",
                    "--profile", str(out)])
    assert "profile:" in capsys.readouterr().out
    (path,) = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU" for line in p.lines for e in line.events}
    assert {"serve_setup", "admit", "prepare", "dispatch", "retire",
            "finish"} <= names
    with open(out / "scopes.json") as f:
        scopes = json.load(f)
    assert scopes and set().union(*(m.values() for m in scopes.values())) \
        <= set(SCOPES)


def test_fallback_count_compiles_nothing_for_a_new_order_of_sizes(server):
    vocab = server.model.config.vocab_size
    outs = [1, 3, 2, 5, 1, 4, 2, 3]
    first = server.serve(_trace(vocab, outs), slots=2, decode_block=2)
    with _Events() as ev:
        again = server.serve(_trace(vocab, outs[::-1]), slots=2,
                             decode_block=2)
    assert ev.n == 0
    assert first.fallback_rounds == again.fallback_rounds == 0
    assert first.tokens == again.tokens == sum(outs)


def test_dispatched_block_table_is_not_the_host_table(server):
    """The loop rewrites its host block table after each asynchronous
    dispatch; what a dispatch was handed must not change with it. (On
    the CPU a device array aliases a 64-byte-aligned host array, so a
    few serves, each with a table of its own, meet that case.)"""
    vocab = server.model.config.vocab_size
    seen = []
    step = server._serve_step_paged_fn

    def keep(*args, steps):
        tables = args[8]
        seen.append((tables, np.array(tables)))
        return step(*args, steps=steps)

    server._serve_step_paged_fn = keep
    try:
        for _ in range(4):
            server.serve(_trace(vocab, [2, 6, 3, 1, 5, 2]), slots=2,
                         decode_block=2, num_blocks=512, tracer=NULL_TRACER)
    finally:
        server._serve_step_paged_fn = step
    assert len(seen) > 16
    for dev, host in seen:
        np.testing.assert_array_equal(np.asarray(dev), host)
