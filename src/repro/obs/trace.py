"""Span tracing: nested, low-overhead wall-clock spans, and the names
the serve program carries on the device (DESIGN.md §14).

``SpanTracer`` is the host-side phase recorder of the observability
layer: the serve loop wraps each phase of a ``serve`` call, the trainer
wraps each coded step, the executor wraps replans and bucket switches,
and the controller wraps its cadence decisions. Every span is

* kept **in memory** (``tracer.spans``, a bounded ring) for tests and
  end-of-run summaries,
* mirrored to the **telemetry JSONL** stream (when the tracer owns a
  ``Telemetry``) as a ``span`` event carrying the monotonic ``t``
  sequence number plus ``perf_counter`` wall stamps (``t0_s`` start,
  ``dur_s`` duration), and
* with ``annotate=True``, opened as a ``jax.profiler.TraceAnnotation``,
  so under ``jax.profiler`` the span lands in the profile's host plane
  on the device's clock, beside the ops the chip ran.

Overhead discipline: a span costs two ``perf_counter`` calls, one list
append and (with telemetry) one JSONL line. Call sites that may run
with tracing off hold ``NULL_TRACER`` — its ``span()`` returns one
shared no-op context manager, so the disabled path is a single
attribute lookup and never allocates (call sites pass no attributes to
``span()`` and set them only when ``tracer.enabled``).

Span taxonomy (DESIGN.md §14). One ``Server.serve`` call is tiled by
its top-level spans: ``serve_setup``, then per round ``admit`` |
``prepare`` | ``prefill_chunk`` or ``decode_chunk`` (holding
``dispatch``) | ``retire``, then ``finish``. Elsewhere: ``replan`` |
``bucket_switch`` | ``adapt_update``.

Device scopes (``SCOPES``, ``LAYER_SCOPES``): the serve program wraps its pieces in
``jax.named_scope``, which changes only the compiled instructions'
``op_name`` metadata. A device profile names ops by instruction alone,
so ``scope_map`` reads the compiled program's text into an instruction
-> scope map, and ``ServeReport.scopes`` carries it for each program a
traced serve ran.
"""
from __future__ import annotations

import re
import time
from collections import deque
from typing import NamedTuple

import jax

__all__ = ["Span", "SpanTracer", "NullTracer", "NULL_TRACER", "SCOPES",
           "LAYER_SCOPES", "scope_of", "scope_map"]

#: the prompt-chunk splice (the ``lax.cond`` branch of the serve program)
SCOPE_PREFILL = "prefill"
#: the loop over layers itself: each layer's slice of the weights and of
#: the KV pool in, the updated pool out (and the copies XLA adds for it)
SCOPE_LAYERS = "model/layers"
#: per layer: KV write into the pool, the gather, and the attend
SCOPE_ATTENTION = "model/attention"
#: the same, in a layer that attends through a window
SCOPE_WINDOW_ATTENTION = "model/window_attention"
#: per layer: the MLP and its norm
SCOPE_MLP = "model/mlp"
#: per layer: the routed expert layer and its norm (the slice of the
#: layer's stacked expert weights, router, sort, grouped matmuls, combine)
SCOPE_MOE = "model/moe"
#: final norm and the logits matmul
SCOPE_UNEMBED = "model/unembed"
#: ``CodedLMHead.encode_logits``: the (nb x kb) float32 block mix
SCOPE_MIX = "coded_head/mix"
#: the straggler finish-mask draw and the block-erasure mask
SCOPE_FINISH_MASK = "coded_head/finish_mask"
#: the erasure solve: survivor gather, LU, two solves, refinement
SCOPE_SOLVE = "coded_head/solve"
#: the fallback ``where`` and the ``argmax`` that picks each token
SCOPE_SAMPLE = "sample"
#: the scopes of a dense, full-attention model's serve program
SCOPES = (SCOPE_PREFILL, SCOPE_LAYERS, SCOPE_ATTENTION, SCOPE_MLP,
          SCOPE_UNEMBED, SCOPE_MIX, SCOPE_FINISH_MASK, SCOPE_SOLVE,
          SCOPE_SAMPLE)
#: named as well by a model with window layers or routed experts (whose
#: layers name ``model/moe`` in place of ``model/mlp``)
LAYER_SCOPES = (SCOPE_WINDOW_ATTENTION, SCOPE_MOE)

_SCOPE_SEGMENTS = tuple((s, tuple(s.split("/"))) for s in SCOPES + LAYER_SCOPES)
#: one instruction of an HLO module's text: its name and the rest
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%?[^\s=]+)\s*=\s*(.*)$", re.M)
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
#: the opcode and its operands (a shape's own parentheses follow no space)
_CALL = re.compile(r"\s([\w-]+)\(([^)]*)\)")
_OPERAND = re.compile(r"%[^\s,()]+")


def scope_of(op_name: str) -> str | None:
    """The innermost of ``SCOPES`` or ``LAYER_SCOPES`` on an op's name
    stack, or None.
    Matches whole segments, so ``jit(prefill)`` is not ``prefill``."""
    parts = op_name.split("/")
    found = None
    i = 0
    while i < len(parts):
        for name, seg in _SCOPE_SEGMENTS:
            if tuple(parts[i:i + len(seg)]) == seg:
                found = name
                i += len(seg)
                break
        else:
            i += 1
    return found


def scope_map(hlo_text: str) -> dict:
    """Instruction name (as a device profile names its op, ``%fusion.12``)
    -> ``scope_of`` its ``op_name``, over a compiled module's text.

    A fusion carries the metadata of the instruction it is named for. An
    instruction with no metadata at all (a copy XLA inserted) takes the
    scope of its first operand that has one. Instructions in no scope are
    left out."""
    out = {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        m = _OP_NAME.search(rest)
        if m is not None:
            scope = scope_of(m.group(1))
        else:
            call = _CALL.search(" " + rest)
            operands = _OPERAND.findall(call.group(2)) if call else ()
            scope = next((out[o] for o in operands if o in out), None)
        if scope is not None:
            out[name] = scope
    return out


class Span(NamedTuple):
    """One finished span: name + wall stamps + nesting + attributes.

    A named tuple: the serve loop makes several a dispatch, and a tuple
    is made in a fraction of a frozen dataclass's time.
    """

    name: str
    t0_s: float  # perf_counter at entry
    dur_s: float
    depth: int  # 0 = top-level
    parent: str | None  # enclosing span's name (None at depth 0)
    attrs: dict


class _NullSpan:
    """Shared no-op context manager: the disabled-tracing fast path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        """Attribute setter, ignored (parity with ``_ActiveSpan.set``)."""


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Tracing disabled: every ``span()`` is the same shared no-op."""

    enabled = False
    spans: tuple = ()

    def span(self, name: str, **attrs) -> _NullSpan:
        return _NULL_SPAN


NULL_TRACER = NullTracer()


class _ActiveSpan:
    """Context manager recording one span into its tracer on exit."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._ann = None

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. placed count)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "_ActiveSpan":
        if self._tracer.annotate:
            # the name alone: attributes would be folded into the
            # profile's event name
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._tracer._stack.append(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tracer = self._tracer
        stack = tracer._stack
        stack.pop()
        span = Span(self.name, self._t0, t1 - self._t0, len(stack),
                    stack[-1] if stack else None, self.attrs)
        tracer.spans.append(span)
        tel = tracer.telemetry
        if tel is not None:
            tel.event(
                "span",
                span=span.name,
                t0_s=span.t0_s,
                dur_s=span.dur_s,
                depth=span.depth,
                parent=span.parent,
                attrs=span.attrs,
            )
        return False  # never swallow exceptions

    # exceptions propagate; the span still records its wall time, so a
    # crashing dispatch leaves a trace of where the run died


class SpanTracer:
    """Nested wall-clock spans over an optional ``Telemetry`` sink.

    One tracer per control loop (serve run, trainer); sharing it with
    the loop's executor/controller puts their replan/decision spans on
    the same nesting stack. Not thread-safe — the loops it instruments
    are single-threaded host code.
    """

    enabled = True

    def __init__(self, telemetry=None, *, max_spans: int = 100_000,
                 annotate: bool = False):
        if max_spans <= 0:
            raise ValueError(f"max_spans must be > 0, got {max_spans}")
        self.telemetry = telemetry
        #: open each span as a ``jax.profiler.TraceAnnotation`` too
        self.annotate = annotate
        #: finished spans, oldest dropped past ``max_spans`` (the JSONL
        #: sink, when present, keeps every span regardless)
        self.spans: deque[Span] = deque(maxlen=max_spans)
        self._stack: list[str] = []

    def span(self, name: str, **attrs) -> _ActiveSpan:
        """``with tracer.span("decode_chunk", steps=4): ...``"""
        return _ActiveSpan(self, name, attrs)

    def summary(self) -> dict:
        """Per-name aggregate: count, total/mean/max seconds."""
        agg: dict[str, dict] = {}
        for s in self.spans:
            a = agg.setdefault(
                s.name, {"count": 0, "total_s": 0.0, "max_s": 0.0}
            )
            a["count"] += 1
            a["total_s"] += s.dur_s
            a["max_s"] = max(a["max_s"], s.dur_s)
        for a in agg.values():
            a["mean_s"] = a["total_s"] / a["count"]
        return agg
