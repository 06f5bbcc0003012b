"""Step tracing: host-side spans, Chrome-trace export, and named scopes
on the device (DESIGN.md §11).

A :class:`Tracer` records **host-timed spans** — begin/end wall-clock
pairs with nesting — as structured events, and exports them in the
Chrome trace-event JSON format (``chrome://tracing`` / Perfetto:
``{"traceEvents": [{"ph": "X", "ts", "dur", "name", ...}]}``).

Two ways to open a span:

* ``tracer.span("step", step=i)`` — explicit, used by the launchers
  around the jitted train/serve step (the caller holds the tracer);
* ``phase("dispatch")`` — the module-level hook the instrumented hot
  path (``repro.plan.exchange``, the model, the train step) calls. What
  it returns depends on where it runs:

  - inside a jax trace (``jit``/``scan``/``shard_map`` bodies, where the
    Python code runs at trace time and a host timestamp would mean
    nothing) it opens ``jax.named_scope(name)``: every operation traced
    in its body carries ``name`` in its ``op_name`` metadata, through
    the backward pass (``transpose(jvp(...))``) and rematerialisation,
    into the compiled executable's HLO and so to each device operation
    of a profiler trace. Scopes exist at trace and compile time only:
    the compiled step runs as fast with them as without;
  - outside a jax trace with a tracer :func:`activate`\\ d, it records a
    host span and also opens ``jax.profiler.TraceAnnotation(name)``, so
    the span lands on the JAX profiler's host plane, on the device
    trace's clock;
  - outside a jax trace with no tracer, it is the shared no-op
    :data:`NULL_SPAN`.

Fencing: jax dispatch is asynchronous, so a host timestamp right after
an op returns measures *launch*, not completion. With
``Tracer(fence=True)`` the ``--trace`` mode of the launchers,
``span.fence(value)`` calls ``jax.block_until_ready`` on the value at
the phase boundary, making the span's duration the real device time of
the phase (single-process backends; the fence is skipped for abstract
tracers). A named scope's ``fence`` and ``set`` do nothing. Untraced
host calls pay a module-global ``None`` check and a trace-state check
per ``phase()`` call — the <5% overhead budget ``benchmarks/
fig_calibration.py`` asserts.

Exclusive time: every completed span records ``self_us`` (duration
minus the duration of its direct children), so a parent's inclusive
time is always ≥ the sum of its children's exclusive times — the
invariant the 8-device trace test asserts.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional


# Synthetic Chrome-trace thread ids for device-tagged spans: host tids
# are masked to 16 bits, so rows at 0x10000+ can never collide.
DEVICE_TID_BASE = 0x10000


def _now_us() -> float:
    return time.perf_counter_ns() / 1e3


def _trace_state_clean() -> bool:
    """True when NOT inside a jax trace (jit/scan/shard_map body) — the
    only place a host-side timestamp means anything. Always True when
    jax is not imported at all (pure host spans)."""
    if "jax" not in sys.modules:
        return True
    from jax._src import core
    return core.trace_state_clean()


def _block(value):
    """``block_until_ready`` on every concrete array leaf of ``value``;
    abstract tracers and non-array leaves are skipped, and an error the
    device raises propagates to the caller."""
    jax = sys.modules.get("jax")
    if jax is None:
        return value
    for leaf in jax.tree.leaves(value):
        if not isinstance(leaf, jax.core.Tracer) \
                and hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return value


class _Span:
    """One open span. Context manager; records an ``"X"`` (complete)
    event on exit, and holds a ``jax.profiler.TraceAnnotation`` of the
    same name open meanwhile (when jax is imported)."""
    __slots__ = ("tracer", "name", "cat", "args", "t0", "child_us",
                 "parent", "annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = 0.0
        self.child_us = 0.0
        self.parent: Optional["_Span"] = None
        self.annotation = None

    def set(self, **kw) -> "_Span":
        self.args.update(kw)
        return self

    def fence(self, value):
        """Block on ``value`` (when fencing is active) so the span's end
        timestamp covers the device work that produced it. Returns the
        value unchanged either way."""
        if self.tracer.fence:
            value = _block(value)
        return value

    def __enter__(self) -> "_Span":
        stack = self.tracer._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        jax = sys.modules.get("jax")
        if jax is not None:
            self.annotation = jax.profiler.TraceAnnotation(self.name)
            self.annotation.__enter__()
        self.t0 = _now_us()
        return self

    def __exit__(self, *exc) -> bool:
        dur = _now_us() - self.t0
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
            self.annotation = None
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if self.parent is not None:
            self.parent.child_us += dur
        self.tracer._record({
            "name": self.name, "cat": self.cat, "ph": "X",
            "ts": self.t0, "dur": dur, "pid": self.tracer.pid,
            "tid": threading.get_ident() & 0xFFFF,
            "args": {**self.args,
                     "self_us": max(0.0, dur - self.child_us)},
        })
        return False


class _ScopeSpan:
    """Span returned inside a jax trace: ``jax.named_scope(name)`` for
    the body, so the operations traced there carry ``name`` in their
    ``op_name``. No host time is taken; ``set`` and ``fence`` do
    nothing."""
    __slots__ = ("scope",)

    def __init__(self, name: str):
        import jax
        self.scope = jax.named_scope(name)

    def __enter__(self) -> "_ScopeSpan":
        self.scope.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self.scope.__exit__(*exc)
        return False

    def set(self, **_kw) -> "_ScopeSpan":
        return self

    def fence(self, value):
        return value


class _NullSpan:
    """Inert span returned outside a jax trace when no tracer is active.
    One shared instance; every method is a no-op."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **_kw) -> "_NullSpan":
        return self

    def fence(self, value):
        return value


NULL_SPAN = _NullSpan()


class Tracer:
    """Host-side span recorder with Chrome-trace export.

    ``fence=True`` makes ``span.fence(x)`` block on device values at
    phase boundaries (the ``--trace`` launcher mode); with ``fence=False``
    spans are pure host intervals (async launch times).
    """

    def __init__(self, *, fence: bool = False):
        self.fence = fence
        self.pid = os.getpid()
        self.events: List[Dict[str, Any]] = []
        self._tls = threading.local()
        self._lock = threading.Lock()

    # -- recording -----------------------------------------------------------
    def _stack(self) -> List[_Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = []
            self._tls.stack = stack
        return stack

    def _record(self, event: Dict[str, Any]) -> None:
        with self._lock:
            self.events.append(event)

    def span(self, name: str, cat: str = "phase", **args) -> _Span:
        return _Span(self, name, cat, args)

    # -- views ---------------------------------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        """Completed ``"X"`` events (optionally filtered by name), in
        completion order."""
        return [e for e in self.events
                if e["ph"] == "X" and (name is None or e["name"] == name)]

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-name aggregate: count, inclusive total, exclusive total
        (µs). Exclusive = duration minus direct children — sums to wall
        time without double counting."""
        out: Dict[str, Dict[str, float]] = {}
        for e in self.spans():
            s = out.setdefault(e["name"],
                               {"count": 0, "total_us": 0.0,
                                "self_us": 0.0})
            s["count"] += 1
            s["total_us"] += e["dur"]
            s["self_us"] += e["args"].get("self_us", e["dur"])
        return out

    # -- export --------------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (``traceEvents`` array of
        events each carrying the required ``ph``/``ts``/``name`` — and
        ``dur`` for complete events).

        Spans tagged with a ``device`` arg (the eager per-device
        exchange probe) are remapped onto synthetic per-device ``tid``
        rows with ``thread_name`` metadata, so Perfetto shows the
        devices side-by-side instead of flattening them onto the host
        thread — stragglers become visible as the one long row."""
        events: List[Dict[str, Any]] = []
        device_rows: Dict[int, int] = {}   # device index -> (pid, tid)
        for e in self.events:
            dev = e.get("args", {}).get("device")
            if e["ph"] == "X" and isinstance(dev, int):
                e = dict(e)
                e["tid"] = DEVICE_TID_BASE + dev
                device_rows[dev] = e["pid"]
            events.append(e)
        for dev in sorted(device_rows):
            events.append({"name": "thread_name", "ph": "M", "ts": 0.0,
                           "pid": device_rows[dev],
                           "tid": DEVICE_TID_BASE + dev,
                           "args": {"name": f"device {dev}"}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write(self, path) -> None:
        from pathlib import Path
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.to_chrome(), indent=1))


# ---------------------------------------------------------------------------
# module-level hook (the instrumented hot path calls this)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[Tracer] = None


def activate(tracer: Tracer) -> Tracer:
    """Install ``tracer`` as the process-wide :func:`phase` sink."""
    global _ACTIVE
    _ACTIVE = tracer
    return tracer


def deactivate() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> Optional[Tracer]:
    return _ACTIVE


def phase(name: str, cat: str = "phase", **args):
    """Span hook for instrumented library code: ``embed``, ``attention``,
    ``router``, ``plan_build`` / ``condense``, ``exchange`` / ``dispatch``
    / ``expert_ffn`` / ``combine``, ``lm_head``, ``optimizer``.

    Inside a jax trace it returns a ``jax.named_scope(name)`` span, so
    the compiled step names its operations (jitted/scanned bodies never
    record compile-time timestamps). Outside one it returns a host span
    of the active tracer, or :data:`NULL_SPAN` (free) when none is
    active."""
    if not _trace_state_clean():
        return _ScopeSpan(name)
    tracer = _ACTIVE
    if tracer is None:
        return NULL_SPAN
    return tracer.span(name, cat, **args)
