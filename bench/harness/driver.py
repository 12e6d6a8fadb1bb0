"""Drive the engine with a traffic generator on the host's monotonic clock.

The loop submits every request that is due, then runs one ``engine.step()``
if the engine has work, and otherwise waits for the next arrival. Each call
into the engine is recorded as a host span (name, start, end); in a traced
run the same spans also go into the profiler's trace as
``TraceAnnotation``s, so idle gaps on the device can be tied to what the
host was doing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional

import numpy as np

from harness.traffic import Generator, Item

STEP, SUBMIT, WAIT = "bench.step", "bench.submit", "bench.wait_arrival"


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    launched: bool = False  # steps only: the step ran a device launch


@dataclasses.dataclass(eq=False)
class Sent:
    """A request as the harness saw it."""

    item: Item
    request: object
    due: float  # absolute monotonic time
    submitted: float
    admitted: Optional[float] = None  # first seen out of the queue
    finished: Optional[float] = None


class Driver:
    def __init__(self, engine, gen: Generator, annotate: bool = False):
        self.engine = engine
        self.gen = gen
        self.annotate = annotate
        self.spans: list = []
        self.sent: list = []
        self.live: list = []
        self.queued: list = []
        self.origin: Optional[float] = None

    def _span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self, now: Optional[float] = None) -> None:
        self.origin = time.monotonic() if now is None else now
        self.gen.start()

    def mark(self) -> tuple:
        return time.monotonic(), dict(self.engine.stats)

    def _submit_due(self, now: float) -> None:
        from repro.launch.serve import Request

        items = self.gen.pop_due(now - self.origin)
        if not items:
            return
        t0 = time.monotonic()
        with self._span(SUBMIT):
            for it in items:
                req = Request(it.prompt, max_new=it.max_new)
                s = Sent(it, req, self.origin + it.due, time.monotonic())
                self.sent.append(s)
                self.live.append(s)
                self.queued.append(s)
                self.engine.submit(req)
        self.spans.append(Span(SUBMIT, t0, time.monotonic()))

    def _after_step(self, now: float) -> None:
        if self.queued:
            still = []
            for s in self.queued:
                if s.request.status == "QUEUED":
                    still.append(s)
                else:
                    s.admitted = now
            self.queued = still
        live = []
        for s in self.live:
            if s.request.done:
                s.finished = now
                self.gen.finished(s.item, list(s.request.out), now - self.origin)
            else:
                live.append(s)
        self.live = live

    def busy(self) -> bool:
        e = self.engine
        return bool(e.queue) or any(s is not None for s in e.slots)

    def run_until(self, t_end: float, stop=None) -> None:
        """Drive until the monotonic clock passes ``t_end`` (or ``stop()``)."""
        while True:
            now = time.monotonic()
            if now >= t_end or (stop is not None and stop()):
                return
            self._submit_due(now)
            if self.busy():
                steps0 = self.engine.stats["decode_steps"]
                t0 = time.monotonic()
                with self._span(STEP):
                    self.engine.step()
                t1 = time.monotonic()
                self.spans.append(Span(STEP, t0, t1,
                                       self.engine.stats["decode_steps"] > steps0))
                self._after_step(t1)
                continue
            nxt = self.gen.next_due()
            wake = t_end if nxt is None else min(t_end, self.origin + nxt)
            t0 = time.monotonic()
            with self._span(WAIT):
                time.sleep(max(0.0, wake - t0))
            self.spans.append(Span(WAIT, t0, time.monotonic()))

    def steps_in(self, w0: float, w1: float) -> list:
        return [s for s in self.spans if s.name == STEP and s.t0 >= w0 and s.t1 <= w1]

    def token_times(self) -> list:
        return [np.asarray(s.request.token_times, np.float64) for s in self.sent]
