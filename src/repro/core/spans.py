"""Spans: the timed phases of a run, recorded where they happen.

A worker task installs a `Recorder` for the thread that runs it
(`recording`); code below it opens spans with ``with span(name, **attrs)``
at task, phase and kernel-call grain, never per row. Each span keeps:

- its name, the run and task ids of the recorder, the index of its parent
  span (-1 for the root);
- ``start``: its start on the wall clock, ``time.time()``, the clock of
  ``Event.ts`` and of the JAX profiler's ``profile_start_time``;
- ``wall``: its seconds by ``perf_counter``; ``cpu``: the seconds its
  thread ran (``time.thread_time``; None for an interval that spans
  threads, such as a task's wait for a worker);
- ``attrs``: a few attributes and counters (rows, bytes, kernel name).
  ``compiles`` counts the XLA compilations and persistent-cache loads that
  ran while the span was the innermost open one on its thread.

The recorder's spans travel as one ``spans`` Event per task
(``core/runtime.py``). Outside a recorder, `span` is a no-op that costs a
context-variable lookup. While a JAX profiler session is active, each span
also enters ``jax.profiler.TraceAnnotation(name)``, so a trace viewer shows
it on the host plane, on the profiler's clock.

This module imports nothing of the repo, so the operators
(``columnar/compute.py``, ``kernels/ops.py``) can use it.
"""
from __future__ import annotations

import contextvars
import sys
import threading
import time
from typing import Dict, List, Optional

# jax.monitoring events that mean "a program was compiled, or loaded from
# the persistent cache"
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_hits")

_CURRENT: contextvars.ContextVar[Optional["Recorder"]] = \
    contextvars.ContextVar("repro_spans", default=None)
_listening = False
_listen_lock = threading.Lock()


class Recorder:
    """The spans of one task (or of one run's planning), on one thread."""

    __slots__ = ("run", "task", "spans", "stack", "root")

    def __init__(self, run: str = "", task: str = ""):
        self.run = run
        self.task = task
        self.spans: List[Dict] = []
        self.stack: List["_Span"] = []      # open spans, innermost last
        self.root: Optional["_Span"] = None

    def drain(self) -> List[Dict]:
        """The spans, stamped with the run and task ids; the recorder
        forgets them. Call it once the root is closed."""
        out, self.spans = self.spans, []
        for s in out:
            s["run"], s["task"] = self.run, self.task
        return out


class _Span:
    __slots__ = ("_rec", "_idx", "_t0", "_c0", "_note", "name", "attrs",
                 "wall")

    def __init__(self, rec: Recorder, name: str, attrs: Dict):
        self._rec = rec
        self._idx = -1
        self._note = None
        self.name = name
        self.attrs = attrs
        self.wall: Optional[float] = None

    def __enter__(self) -> "_Span":
        rec = self._rec
        _listen()
        self._idx = len(rec.spans)
        rec.spans.append({"name": self.name,
                          "parent": rec.stack[-1]._idx if rec.stack else -1,
                          "start": time.time(), "wall": None, "cpu": None,
                          "attrs": self.attrs})
        rec.stack.append(self)
        if _profiler_active():
            import jax

            self._note = jax.profiler.TraceAnnotation(self.name)
            self._note.__enter__()
        self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def close(self) -> None:
        """End the span (and any span still open inside it); a second
        call does nothing."""
        if self.wall is not None or self._idx < 0:
            return
        stack = self._rec.stack
        while stack and stack[-1] is not self:
            stack[-1].close()
        if stack:
            stack.pop()
        t1, c1 = time.perf_counter(), time.thread_time()
        self.wall = t1 - self._t0
        record = self._rec.spans[self._idx]
        record["wall"], record["cpu"] = self.wall, c1 - self._c0
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)


class _NoSpan:
    __slots__ = ()
    wall = None

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def close(self) -> None:
        pass

    def set(self, **attrs) -> None:
        pass


_NOSPAN = _NoSpan()


def span(name: str, **attrs):
    """A span of the current recorder; a no-op outside one."""
    rec = _CURRENT.get()
    if rec is None:
        return _NOSPAN
    return _Span(rec, name, attrs)


def current() -> Optional[Recorder]:
    return _CURRENT.get()


class recording:
    """Install a recorder for this thread and open its root span, which
    `finish` (or the end of the block) closes. The block yields the
    recorder."""

    def __init__(self, run: str, task: str, root: str, **attrs):
        self.rec = Recorder(run, task)
        self._root = _Span(self.rec, root, attrs)
        self._token = None

    def __enter__(self) -> Recorder:
        self._token = _CURRENT.set(self.rec)
        self.rec.root = self._root.__enter__()
        return self.rec

    def __exit__(self, *exc) -> bool:
        self._root.close()
        _CURRENT.reset(self._token)
        return False


def finish(**attrs) -> Optional[float]:
    """Close the current recorder's root span with `attrs`; its wall
    seconds, or None outside a recorder."""
    rec = _CURRENT.get()
    if rec is None or rec.root is None:
        return None
    rec.root.set(**attrs)
    rec.root.close()
    return rec.root.wall


def interval(name: str, start: float, wall: float, run: str, task: str,
             **attrs) -> Dict:
    """A closed span measured by hand across threads (no CPU reading)."""
    return {"name": name, "parent": -1, "start": start, "wall": wall,
            "cpu": None, "attrs": attrs, "run": run, "task": task}


# ---------------------------------------------------------------------------
# the JAX side: the compile counter and the profiler's host plane
# ---------------------------------------------------------------------------


def _profiler_active() -> bool:
    prof = sys.modules.get("jax._src.profiler")
    state = getattr(prof, "_profile_state", None)
    return getattr(state, "profile_session", None) is not None


def _count_compile(name: str, *args, **kwargs) -> None:
    if name not in _COMPILE_EVENTS:
        return
    rec = _CURRENT.get()
    if rec is not None and rec.stack:
        attrs = rec.stack[-1].attrs
        attrs["compiles"] = attrs.get("compiles", 0) + 1


def _listen() -> None:
    """Register the compile listener once, as soon as JAX is loaded."""
    global _listening
    if _listening or "jax" not in sys.modules:
        return
    with _listen_lock:
        if _listening:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        jax.monitoring.register_event_listener(_count_compile)
        _listening = True
