"""The client side of a run: its clock, what it records of each request,
the compile watcher, the tracer that mirrors program spans into the
profiler's trace, and the open- and closed-loop drivers of the program's
`AsyncServer`.
"""
from __future__ import annotations

import asyncio
import math
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax


class Clock:
    """Monotonic seconds from an origin the run sets, so that the window
    opens at 0. ``sleep`` is where the server waits for the next arrival;
    while tracing it appears in the trace as ``await_arrival``."""

    def __init__(self):
        self.origin = time.monotonic()
        self.annotate = False

    def now(self) -> float:
        return time.monotonic() - self.origin

    async def sleep(self, dt: float) -> None:
        if self.annotate:
            with jax.profiler.TraceAnnotation("await_arrival"):
                await asyncio.sleep(max(dt, 0.0))
        else:
            await asyncio.sleep(max(dt, 0.0))


@dataclass
class Record:
    rid: int
    prompt: object                 # the prompt's token ids
    max_new: int
    arrival: float                 # scheduled (open loop) or sent (closed)
    sent: float = math.nan         # when the server took it in
    admit: float = math.nan
    tokens: list = field(default_factory=list)   # stamp of every token
    finish: float = math.nan

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def done(self) -> bool:
        return not math.isnan(self.finish)


class Recorder:
    """Stands in for the program's `Telemetry` in `AsyncServer`: the same
    lifecycle hooks, stamped on the client's clock, keeping every token's
    stamp. ``tick_tokens`` collects the tokens of the current scheduler
    tick for the traced run's accounting."""

    def __init__(self, clock: Clock):
        self.clock = clock
        self.records: dict[int, Record] = {}
        self.tick_tokens: list[tuple[int, int]] | None = None   # if traced

    def expect(self, req) -> None:
        """A request of the schedule, recorded before it is sent, so that
        one never sent still counts."""
        self.records[req.idx] = Record(
            req.idx, req.prompt, req.max_new_tokens,
            math.nan if req.at is None else float(req.at))

    def on_enqueue(self, rid, *, priority=0, at=None):
        r = self.records[rid]
        r.sent = self.clock.now()
        if math.isnan(r.arrival):
            r.arrival = r.sent

    # a request of an earlier window that is still in flight has no
    # record any more: its events are not this window's
    def on_admit(self, rid, at=None):
        r = self.records.get(rid)
        if r is not None and math.isnan(r.admit):
            r.admit = self.clock.now()

    def on_token(self, rid, at=None):
        r = self.records.get(rid)
        if r is not None:
            if self.tick_tokens is not None:
                self.tick_tokens.append((rid, len(r.tokens)))
            r.tokens.append(self.clock.now())

    def on_preempt(self, rid, at=None):
        pass

    def on_finish(self, rid, at=None):
        r = self.records.get(rid)
        if r is not None:
            r.finish = self.clock.now()

    def report(self, slo_ms=None):
        return {}


class CompileWatch:
    """Counts backend compiles and persistent-cache hits and misses through
    `jax.monitoring` (a copy of the program's chip_smoke watcher). A program
    loaded from the persistent cache counts as a compile too, so
    ``compiles`` is every program built; ``misses`` the ones compiled."""

    def __init__(self):
        self.compiles = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs


def annotating_tracer(clock: Clock, recorder: Recorder):
    """A subclass of the program's `Tracer` that opens a
    `jax.profiler.TraceAnnotation` around each span and dispatch, so the
    program's own spans land on the device trace's clock, and keeps one
    entry per scheduler tick: its host times, its dispatches with their
    arguments, and the tokens it streamed."""
    from repro.serving.trace import Tracer

    class AnnotatingTracer(Tracer):
        def __init__(self):
            super().__init__(clock, capacity=1)
            self.ticks: list[dict] = []
            self._dispatches: list[tuple] = []
            recorder.tick_tokens = []

        @contextmanager
        def span(self, name, **kw):
            t0 = clock.now()
            with jax.profiler.TraceAnnotation(name), super().span(name, **kw):
                yield
            if name == "tick":
                self.ticks.append({"t0": t0, "t1": clock.now(),
                                   "dispatches": self._dispatches,
                                   "tokens": recorder.tick_tokens})
                self._dispatches, recorder.tick_tokens = [], []

        @contextmanager
        def dispatch(self, kind, **kw):
            with jax.profiler.TraceAnnotation(kind), \
                    super().dispatch(kind, **kw):
                yield
            self._dispatches.append((kind, dict(kw.get("args") or {})))

    return AnnotatingTracer()


# ------------------------------------------------------------------ loops --

async def _watch(clock, until: float, hooks) -> None:
    """Run ``hooks`` ((time, fn) pairs) between scheduler ticks as the
    clock passes each time. A hook may return another pair, which is
    scheduled in turn."""
    pending = sorted(hooks, key=lambda h: h[0])
    while pending:
        at, fn = pending.pop(0)
        while clock.now() < at:
            await asyncio.sleep(min(0.005, max(at - clock.now(), 0.0)))
        nxt = fn()
        if nxt is not None:
            pending = sorted(pending + [nxt], key=lambda h: h[0])


def submit_kwargs(req) -> dict:
    return dict(prompt=req.prompt, max_new_tokens=req.max_new_tokens,
                rid=req.idx)


async def drive_open(server, recorder, schedule, *, deadline: float,
                     hooks=()) -> None:
    """Send ``schedule`` at its arrival times and serve until every request
    has finished or the clock passes ``deadline``."""
    for req in schedule:
        recorder.expect(req)
    arrivals = [(req.at, submit_kwargs(req)) for req in schedule]
    watcher = asyncio.ensure_future(_watch(server.clock, deadline, hooks))
    try:
        await asyncio.wait_for(server.run(arrivals),
                               timeout=max(deadline - server.clock.now(), 0))
    except asyncio.TimeoutError:
        pass
    finally:
        watcher.cancel()


async def drive_closed(server, recorder, traffic, *, lanes: int,
                       close: float, deadline: float, hooks=()) -> None:
    """Keep ``traffic.outstanding(lanes)`` requests in flight until the
    window closes, then serve until all have finished or ``deadline``."""
    handles = []

    def send():
        req = traffic.next_closed()
        recorder.expect(req)
        return server.submit(**submit_kwargs(req))

    async def clients():
        handles.extend(send() for _ in range(traffic.outstanding(lanes)))
        while server.clock.now() < close:
            await asyncio.sleep(0)
            for i, h in enumerate(handles):
                if h.done and server.clock.now() < close:
                    handles[i] = send()

    watcher = asyncio.ensure_future(_watch(server.clock, deadline, hooks))
    client = asyncio.ensure_future(clients())
    await asyncio.sleep(0)
    try:
        await asyncio.wait_for(server.run(),
                               timeout=max(deadline - server.clock.now(), 0))
        await client
    except asyncio.TimeoutError:
        pass
    finally:
        client.cancel()
        watcher.cancel()
