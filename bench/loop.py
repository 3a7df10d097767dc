"""Open-loop arrivals through the program's own scheduler loop.

The scheduler drains an admission queue through ``len``, ``popleft`` and
``appendleft`` only.  :class:`DueQueue` holds every request of a run and
shows the scheduler only those whose due time has passed, with each
request's ``submit_time`` set to its due time, so every latency runs from the
moment the request was due.  When ``ContinuousScheduler.run`` returns
because the system has drained, :func:`drive` sleeps to the next due time
and calls it again.

:class:`Sink` is the program's ``Telemetry`` collector, installed in every
run, traced or not.  It adds per-tick snapshots of the two tiers' prefix
counters, and it opens and closes the profiler trace from inside the loop.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Optional

from repro.serving.telemetry import Telemetry


class DueQueue:
    """Admission queue of ``AdmittedRequest``s sorted by ``submit_time``
    (monotonic due time).  Once ``stop()`` returns true it releases no more
    requests."""

    def __init__(self, adms, stop: Optional[Callable[[], bool]] = None):
        self._all = list(adms)
        self._next = 0
        self._ready: deque = deque()
        self._stop = stop
        self.closed = False

    def _release(self) -> None:
        if not self.closed and self._stop is not None and self._stop():
            self.closed = True
        if self.closed:
            return
        now = time.monotonic()
        while self._next < len(self._all) and self._all[self._next].submit_time <= now:
            self._ready.append(self._all[self._next])
            self._next += 1

    def __len__(self) -> int:
        self._release()
        return len(self._ready)

    def __getitem__(self, i):
        return self._ready[i]

    def popleft(self):
        return self._ready.popleft()

    def appendleft(self, adm) -> None:
        self._ready.appendleft(adm)

    def next_due(self) -> Optional[float]:
        """Due time of the next unreleased request; None once closed or
        exhausted."""
        self._release()
        if self.closed or self._next >= len(self._all):
            return None
        return self._all[self._next].submit_time


class Sink(Telemetry):
    """The program's telemetry plus per-tick prefix-counter snapshots and the
    profiler window.  ``trace`` = (start, stop, directory) in monotonic
    seconds starts and stops ``jax.profiler`` at the first tick boundaries
    past those times; ``clock`` then holds the monotonic time of the
    ``bench_clock`` annotation, which puts this clock on the trace's."""

    def __init__(self, trace=None):
        super().__init__()
        self.sched = None
        self.saved = []              # per tick: (t0, {tier: (tokens_saved, full_hits)})
        self.done_at = {}            # rid -> monotonic time of the terminal span
        self.pending = set()         # request ids still waiting for an answer
        self.trace = trace
        self.tracing = False
        self.traced = None           # (start, stop) monotonic of the traced span
        self.clock = None

    def req_terminal(self, rid: int, record) -> None:
        super().req_terminal(rid, record)
        self.done_at[rid] = self.traces[rid].spans[-1].t0
        self.pending.discard(rid)

    def pool_counters(self) -> dict:
        """Per tier: (prompt tokens served from the prefix index, whole-prompt
        hits) so far."""
        out = {}
        for name, rt in (("S", self.sched.srt), ("L", self.sched.lrt)):
            st = rt.pool.stats
            out[name] = (st.get("tokens_saved", 0), st.get("full_hits", 0))
        return out

    def end_tick(self, gauges) -> None:
        tick = self._tick
        super().end_tick(gauges)
        if tick is not None and self.sched is not None:
            self.saved.append((tick.t0, self.pool_counters()))
        if self.trace is not None:
            self._profile()

    def _profile(self) -> None:
        import jax

        start, stop, directory = self.trace
        now = time.monotonic()
        if not self.tracing and self.traced is None and now >= start:
            jax.profiler.start_trace(directory)
            self.tracing = True
            with jax.profiler.TraceAnnotation("bench_clock"):
                self.clock = time.monotonic()
            self.traced = (time.monotonic(), None)
        elif self.tracing and now >= stop:
            self.traced = (self.traced[0], time.monotonic())
            jax.profiler.stop_trace()
            self.tracing = False

    def finish(self) -> None:
        """Stop a trace the run ended inside of."""
        if self.tracing:
            import jax
            self.traced = (self.traced[0], time.monotonic())
            jax.profiler.stop_trace()
            self.tracing = False


def drive(sched, queue: DueQueue, theta: float) -> tuple[dict, list]:
    """Serve ``queue`` open-loop; returns (result records, seconds each wake
    from an idle system came after the due time it slept to)."""
    results, late = {}, []
    while True:
        results.update(sched.run(queue, theta=theta))
        due = queue.next_due()
        if due is None:
            return results, late
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        late.append(time.monotonic() - due)
