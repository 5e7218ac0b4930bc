"""Host-side tracing: span/counter registry with JSONL export.

The measurement substrate of the control plane.  The in-graph half of the
observability subsystem (``repro.obs.telemetry``) meters what happens
*inside* the fused rollout; this module meters everything around it --
wall-clock spans of dispatch/train/serve/service phases, point events
(the trainer's ``ffr_shed`` / ``grid_ckpt`` markers, the serving loop's
batch-thinning), and scalar counters/observations -- and exports all of
it as machine-readable JSONL so ``python -m repro.obs.report`` (or any
``jq`` one-liner) can render latency tables from a run after the fact.

Design constraints, in order:

  * zero setup: a module-level default :class:`Tracer` (``obs.trace.span``
    / ``obs.trace.event`` / ``obs.metrics``) so call sites are one-liners,
  * on the profiler's clock: every span also holds a
    ``jax.profiler.TraceAnnotation`` of the same name open for its whole
    duration, so inside a ``jax.profiler`` trace (:func:`profile`) the
    program's spans sit on the host plane beside the device's ``XLA
    Modules`` line and device idle time can be laid against them,
  * cheap enough for per-trigger use: a span is a small slotted context
    manager -- two ``perf_counter`` calls and one append of the span to a
    ring; the record dict is built only when the records are read, and
    the annotation is built only while a profiler records (about 1.5 us
    a span with no profiler running),
  * bounded: ``Tracer.records`` keeps the newest :data:`RECORDS_MAX`
    span/event records in a ring, so an always-on service holds a fixed
    amount of trace whatever its uptime (counters and observation series
    in :class:`Metrics` are the caller's to bound),
  * schema-stable records: every line is one JSON object with a ``kind``
    (``span`` | ``event`` | ``counter`` | ``observation``), a ``name``, a
    unix ``ts``, and a flat ``attrs`` dict; spans add ``wall_s`` (full
    float precision -- sub-10 ms spans are exactly the scale of the
    paper's 97.2 ms claim) and ``parent`` (the enclosing span's name).

:func:`profile` wraps a block in ``jax.profiler.trace`` when a directory
is given (or ``REPRO_JAX_PROFILE_DIR`` is set), so the same call sites
produce device-level traces without code changes.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Iterable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

# span/event records a Tracer keeps (the newest; older ones drop out)
RECORDS_MAX = 65_536


class Metrics:
    """Counter + observation registry (host-side scalars).

    ``inc`` accumulates monotonic counters; ``observe`` appends to a
    per-name series summarised on demand (count/mean/p50/p95/max).
    """

    def __init__(self):
        self._counters: dict[str, float] = {}
        self._series: dict[str, list[float]] = {}
        self._lock = threading.Lock()

    def inc(self, name: str, by: float = 1.0) -> float:
        with self._lock:
            v = self._counters.get(name, 0.0) + float(by)
            self._counters[name] = v
        return v

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self._series.setdefault(name, []).append(float(value))

    def observe_many(self, name: str, values: Iterable[float]) -> None:
        """Append every value to one series under one lock (nothing, and
        no empty series, when there are none)."""
        xs = np.asarray(values, np.float64).ravel().tolist()
        if xs:
            with self._lock:
                self._series.setdefault(name, []).extend(xs)

    @property
    def counters(self) -> dict[str, float]:
        return dict(self._counters)

    def series(self, name: str) -> list[float]:
        """Copy of one observation series (windowed consumers -- e.g. the
        service load generator's timed-phase percentiles -- slice it)."""
        with self._lock:
            return list(self._series.get(name, ()))

    def summary(self, name: str) -> dict:
        xs = np.asarray(self._series.get(name, ()), np.float64)
        if xs.size == 0:
            return dict(name=name, count=0)
        return dict(
            name=name, count=int(xs.size), total=float(xs.sum()),
            mean=float(xs.mean()), min=float(xs.min()), max=float(xs.max()),
            p50=float(np.percentile(xs, 50)),
            p95=float(np.percentile(xs, 95)),
            p99=float(np.percentile(xs, 99)),
        )

    def all_summaries(self) -> list[dict]:
        return [self.summary(n) for n in sorted(self._series)]

    def clear(self) -> None:
        with self._lock:
            self._counters.clear()
            self._series.clear()


class Span:
    """One span: ``with tracer.span(name, **attrs) as attrs``.

    Entering pushes the name on the thread's span stack, opens the
    profiler annotation and starts the clock; leaving stops it, closes
    the annotation and puts the span itself in the tracer's ring, where
    :attr:`Tracer.records` turns it into a record dict when read.
    """

    __slots__ = ("name", "attrs", "parent", "t0", "wall_s",
                 "_ring", "_stack", "_note")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._ring = tracer._ring
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> dict:
        stack = self._stack = _thread_stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        # the annotation only where a profiler is recording: building one
        # costs a third of a span
        self._note = None
        if TraceAnnotation.is_enabled():
            self._note = TraceAnnotation(self.name)
            self._note.__enter__()
        self.t0 = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(*exc)
            self._note = None
        self.wall_s = t1 - self.t0
        self._stack.pop()
        self._ring.append(self)


_LOCAL = threading.local()


def _thread_stack() -> list:
    """The names of the spans open on this thread, innermost last."""
    try:
        return _LOCAL.stack
    except AttributeError:
        _LOCAL.stack = []
        return _LOCAL.stack


class Tracer:
    """Span/event recorder; spans nest per thread.

    The record's ``parent`` is the name of the enclosing span on the same
    thread, of whichever tracer (or None at top level).  The context
    manager yields the record's mutable ``attrs`` dict so call sites can
    attach results discovered mid-span (e.g. the post-shed batch size).
    """

    def __init__(self, metrics: Optional[Metrics] = None):
        # deque.append is atomic: recording takes no lock
        self._ring: deque = deque(maxlen=RECORDS_MAX)
        self.metrics = metrics if metrics is not None else Metrics()
        # unix time of perf_counter's zero: a span's ``ts`` without a
        # second clock read per span
        self._epoch = time.time() - time.perf_counter()

    def _record(self, r) -> dict:
        if not isinstance(r, Span):
            return r
        return dict(kind="span", name=r.name, ts=self._epoch + r.t0,
                    parent=r.parent, attrs=r.attrs, wall_s=r.wall_s)

    @property
    def records(self) -> list[dict]:
        """The kept span/event records, oldest first."""
        return [self._record(r) for r in list(self._ring)]

    # -- recording ---------------------------------------------------------
    def span(self, name: str, **attrs) -> Span:
        """Time a block; record {kind, name, ts, wall_s, parent, attrs}."""
        return Span(self, name, attrs)

    def event(self, name: str, **attrs) -> dict:
        """Record a point event; returns the (mutable) attrs dict."""
        self._ring.append(dict(kind="event", name=name, ts=time.time(),
                               attrs=attrs))
        return attrs

    # -- querying ----------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> list[dict]:
        return [self._record(r) for r in list(self._ring)
                if isinstance(r, Span) and (name is None or r.name == name)]

    def events(self, name: Optional[str] = None) -> list[dict]:
        return [r for r in list(self._ring) if isinstance(r, dict)
                and (name is None or r["name"] == name)]

    # -- export ------------------------------------------------------------
    def export_jsonl(self, path: str) -> str:
        """Write every record plus counter/observation summaries, one JSON
        object per line (the schema the report CLI and CI consume)."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            for rec in self.records:
                f.write(json.dumps(rec, default=float) + "\n")
            for name, v in sorted(self.metrics.counters.items()):
                f.write(json.dumps(dict(kind="counter", name=name,
                                        value=v)) + "\n")
            for s in self.metrics.all_summaries():
                f.write(json.dumps(dict(kind="observation", **s)) + "\n")
        return path

    def clear(self) -> None:
        self._ring.clear()
        self.metrics.clear()


# -- module-level default registry (the one-liner surface) ------------------
_TRACER = Tracer()
metrics = _TRACER.metrics
span = _TRACER.span
event = _TRACER.event


def get_tracer() -> Tracer:
    return _TRACER


def device_context() -> dict:
    """Backend/mesh context stamped into bench reports and traces."""
    import jax

    devs = jax.devices()
    return dict(
        backend=jax.default_backend(),
        n_devices=len(devs),
        device_kind=devs[0].device_kind if devs else "none",
        process_count=jax.process_count(),
    )


PROFILE_ENV = "REPRO_JAX_PROFILE_DIR"


@contextmanager
def profile(out_dir: Optional[str] = None):
    """Opt-in ``jax.profiler`` trace around a block.

    Enabled when ``out_dir`` is given or ``REPRO_JAX_PROFILE_DIR`` is set;
    otherwise a no-op, so call sites can wrap hot paths unconditionally.
    """
    out_dir = out_dir or os.environ.get(PROFILE_ENV)
    if not out_dir:
        yield None
        return
    import jax

    os.makedirs(out_dir, exist_ok=True)
    with jax.profiler.trace(out_dir):
        yield out_dir


def read_jsonl(path: str) -> list[dict]:
    """Load an exported trace (skips blank/corrupt lines defensively)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out
