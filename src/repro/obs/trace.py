"""Query-lifecycle tracing: spans at real dispatch boundaries.

Every request (serving) or batch pipeline invocation (one-shot) carries a
trace ID; `Span`s with monotonic timestamps and structured attributes cover
the lifecycle stages the paper's cost model reasons about:

  admit → probe → feature-extract/estimate → plan-select → resume launches
  (steps, width, compaction — from the persistent driver) → rerank → complete

Design constraints (pinned by tests/test_obs.py):

  * tracing must never enter the jitted hot path — spans are emitted only
    at host-level dispatch points that already exist (an `engine.search`
    call, a persistent-driver launch, a scheduler pump), so results are
    bit-identical with tracing on vs. off and no device synchronization is
    added inside any launch loop;
  * span attributes are plain Python scalars/strings at emit time — a span
    must never retain a live device array (that would pin device memory
    and turn a later repr into a sync);
  * memory is bounded: spans land in a ring (`deque(maxlen=capacity)`);
    an optional JSONL sink streams them out for offline analysis. The sink
    is bounded too — at `sink_max_bytes` the file rotates to `<path>.1`
    (replacing any previous rotation), so total disk use stays ≤ ~2× the
    cap no matter how long the process serves.

The tracer is clock-injected like the serving scheduler: pass `clock=` to
drive it from a virtual clock (benchmarks) or leave the default
`time.perf_counter` (monotonic) for wall-clock tracing.

Profiler bridge: every `span()` of a `Tracer` also enters
`jax.profiler.TraceAnnotation("repro.<name>", trace=<trace id>)`, so while
a JAX profiler trace runs the span lands in its xplane beside the device's
operations; with no trace running the annotation records nothing.
Instants and `emit()`ed intervals stay in the ring only. The xplane's
clock starts at the trace's start while the ring's is `clock`, so a reader
pairs the spans present in both (same name and trace id, in order) to fit
the offset between the two clocks and then places ring-only events on the
device timeline. `NullTracer` never touches `jax.profiler`.

Compile counter: every live `Tracer` hears one process-wide
`jax.monitoring` listener. Each program JAX lowers becomes one `compile`
span (ring only) from the start of its lowering to the end of the backend
compile that follows it; `seconds` is the two durations summed,
`cache_miss` is False where the persistent compilation cache supplied the
executable, and `inside` names the innermost span open on this tracer
when it compiled (its trace id is that span's). `n_compiles` counts one
per program lowered.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import os
import time
import weakref
from collections import deque

import jax
import numpy as np

#: ring default — ~100 B/span of attrs keeps this well under 10 MB
DEFAULT_CAPACITY = 1 << 16

#: sink default — rotate the JSONL file once it reaches 64 MB, keeping one
#: predecessor (`<path>.1`), so a long-running serve process holds at most
#: ~2× this on disk
DEFAULT_SINK_MAX_BYTES = 64 << 20

_SCALARS = (str, int, float, bool, type(None))

# jax.monitoring events of one program's compilation, in the order they fire
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# live tracers the process-wide compile listener reports to
_compile_tracers: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_compile_listening = False


def _listen_for_compiles(tracer: "Tracer") -> None:
    """Register `tracer` with the one process-wide compile listener
    (installed on first use; jax.monitoring listeners live as long as the
    process, so a tracer is held weakly and drops out when collected)."""
    global _compile_listening
    _compile_tracers.add(tracer)
    if _compile_listening:
        return

    def on_duration(event, duration, **_):
        if event in (LOWER_EVENT, BACKEND_EVENT):
            for t in list(_compile_tracers):
                t._compile_event(event, duration)

    def on_event(event, **_):
        if event == CACHE_HIT_EVENT:
            for t in list(_compile_tracers):
                t._compile_event(event, 0.0)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _compile_listening = True


def _host_scalar(v):
    """Coerce an attribute value to a plain host scalar (never a device
    array). numpy scalars become Python numbers; anything array-like is a
    bug at the call site — spans carry summaries, not tensors."""
    if isinstance(v, _SCALARS):
        return v
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.bool_):
        return bool(v)
    raise TypeError(
        f"span attribute of type {type(v).__name__} — spans must carry "
        "plain host scalars (summarize arrays before emitting)")


@dataclasses.dataclass
class Span:
    """One lifecycle interval: [t0, t1] in the tracer's clock units."""

    trace_id: str
    name: str
    t0: float
    t1: float = 0.0
    attrs: dict = dataclasses.field(default_factory=dict)

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def to_json(self) -> str:
        return json.dumps(dict(trace=self.trace_id, name=self.name,
                               t0=self.t0, t1=self.t1, **self.attrs),
                          sort_keys=True)


class Tracer:
    """Bounded in-memory span ring + optional JSONL sink.

    Trace IDs are deterministic counters (``q-000001``) — no RNG, so a
    traced run is replayable and two identically-driven runs produce
    identical span streams (up to timestamps)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 clock=time.perf_counter, sink: str | None = None,
                 sink_max_bytes: int = DEFAULT_SINK_MAX_BYTES):
        self.capacity = capacity
        self._open: list[Span] = []     # spans entered and not yet left
        self._compiling: Span | None = None  # lowered, backend not done
        self.n_compiles = 0
        self.clock = clock
        self._ring: deque[Span] = deque(maxlen=capacity)
        self._ids = itertools.count(1)
        self.n_emitted = 0          # lifetime count (ring may have evicted)
        self._sink_path = sink
        self.sink_max_bytes = int(sink_max_bytes)
        self.n_rotations = 0
        self._sink = open(sink, "a") if sink else None
        # appending to a pre-existing file: count what's already there
        self._sink_bytes = self._sink.tell() if self._sink else 0
        _listen_for_compiles(self)

    # ------------------------------------------------------------- ids ----
    def new_trace(self, prefix: str = "q") -> str:
        return f"{prefix}-{next(self._ids):06d}"

    # ----------------------------------------------------------- record ----
    def emit(self, name: str, trace_id: str = "", t0: float | None = None,
             t1: float | None = None, **attrs) -> Span:
        """Record a completed span (t1 defaults to t0: an instant event)."""
        now = self.clock()
        t0 = now if t0 is None else t0
        t1 = t0 if t1 is None else t1
        sp = Span(trace_id=trace_id, name=name, t0=float(t0), t1=float(t1),
                  attrs={k: _host_scalar(v) for k, v in attrs.items()})
        self._append(sp)
        return sp

    @contextlib.contextmanager
    def span(self, name: str, trace_id: str = "", **attrs):
        """Context manager measuring the enclosed host work. The yielded
        span is mutable — `sp.set(steps=..., ndc=...)` attaches attributes
        discovered during the work (host scalars only)."""
        sp = Span(trace_id=trace_id, name=name, t0=self.clock(),
                  attrs={k: _host_scalar(v) for k, v in attrs.items()})
        self._open.append(sp)
        try:
            with jax.profiler.TraceAnnotation(f"repro.{name}",
                                              trace=trace_id):
                yield sp
        finally:
            sp.t1 = self.clock()
            self._open.pop()
            sp.attrs = {k: _host_scalar(v) for k, v in sp.attrs.items()}
            self._append(sp)

    def _compile_event(self, event: str, duration: float) -> None:
        """One jax.monitoring event of a compilation (see module doc)."""
        if event == LOWER_EVENT:
            self._flush_compile()
            now = self.clock()
            inner = self._open[-1] if self._open else None
            self._compiling = Span(
                trace_id=inner.trace_id if inner else "", name="compile",
                t0=now - duration, t1=now,
                attrs=dict(inside=inner.name if inner else "",
                           seconds=float(duration), cache_miss=True))
            self.n_compiles += 1
        elif self._compiling is not None:
            if event == CACHE_HIT_EVENT:
                self._compiling.attrs["cache_miss"] = False
            else:                                   # the backend compile
                self._compiling.t1 = self.clock()
                self._compiling.attrs["seconds"] += float(duration)
                self._flush_compile()

    def _flush_compile(self) -> None:
        sp, self._compiling = self._compiling, None
        if sp is not None:
            self._append(sp)

    def _append(self, sp: Span) -> None:
        if self._compiling is not None:
            self._flush_compile()
        self._ring.append(sp)
        self.n_emitted += 1
        if self._sink is not None:
            line = sp.to_json() + "\n"
            if (self._sink_bytes > 0
                    and self._sink_bytes + len(line) > self.sink_max_bytes):
                self._rotate_sink()
            self._sink.write(line)
            self._sink_bytes += len(line)

    def _rotate_sink(self) -> None:
        """Roll the sink file to `<path>.1` and start a fresh one. A span
        larger than the cap still lands (a file always takes ≥1 line)."""
        self._sink.close()
        os.replace(self._sink_path, self._sink_path + ".1")
        self._sink = open(self._sink_path, "w")
        self._sink_bytes = 0
        self.n_rotations += 1

    # ------------------------------------------------------------ query ----
    def __len__(self) -> int:
        return len(self._ring)

    def spans(self, trace_id: str | None = None,
              name: str | None = None) -> list[Span]:
        """Spans still in the ring, oldest first, optionally filtered."""
        self._flush_compile()
        return [s for s in self._ring
                if (trace_id is None or s.trace_id == trace_id)
                and (name is None or s.name == name)]

    def clear(self) -> None:
        self._ring.clear()

    # ------------------------------------------------------------- sink ----
    def flush(self) -> None:
        self._flush_compile()
        if self._sink is not None:
            self._sink.flush()

    def close(self) -> None:
        self._flush_compile()
        if self._sink is not None:
            self._sink.close()
            self._sink = None


class _NullSpan:
    """Inert span: accepts attribute writes, records nothing."""

    __slots__ = ("attrs",)

    def __init__(self):
        self.attrs = {}

    def set(self, **attrs):
        return self


class NullTracer:
    """No-op tracer — the default everywhere, so untraced call sites pay
    one attribute lookup and nothing else."""

    capacity = 0
    n_emitted = 0

    def new_trace(self, prefix: str = "q") -> str:
        return ""

    def emit(self, name, trace_id="", t0=None, t1=None, **attrs):
        return _NullSpan()

    @contextlib.contextmanager
    def span(self, name, trace_id="", **attrs):
        yield _NullSpan()

    def __len__(self):
        return 0

    def spans(self, trace_id=None, name=None):
        return []

    def clear(self):
        pass

    def flush(self):
        pass

    def close(self):
        pass


#: shared inert instance — `tr = tracer or NO_TRACE` normalizes call sites
NO_TRACE = NullTracer()


def as_tracer(tracer) -> "Tracer | NullTracer":
    return NO_TRACE if tracer is None else tracer
