"""What the benchmark sees of a run, through the engine's public hooks.

``Recorder`` is the engine's duck-typed ``journal=`` (``record_submit``,
``record_tokens``, ``record_finish``, ``record_reject``, ``flush``): it
timestamps every token as the host receives it and every request's end,
does no I/O, and hands each event to the load generator so that it can
submit the next request or close the run.  ``CompileClock`` counts what
JAX compiles inside a ``with`` block.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax


@dataclasses.dataclass
class Trail:
    """One request as the client saw it."""
    uid: int
    arrival: float  # due time, seconds after the window opened
    prompt_len: int
    measured: bool
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_done: Optional[float] = None
    outcome: Optional[str] = None


class Recorder:
    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.t0 = None
        self.trails: Dict[int, Trail] = {}
        self.on_tokens = None  # callbacks of the load generator
        self.on_finish = None
        self.on_flush = None

    def expect(self, uid, arrival, prompt_len, measured):
        self.trails[uid] = Trail(uid, arrival, prompt_len, measured)

    # ------------------------------------------------ the journal hooks
    def record_submit(self, req):
        pass  # the load generator registered the request already

    def record_tokens(self, uid, toks):
        tr = self.trails.get(uid)
        if tr is None:
            return
        now = self.clock()
        tr.tokens.extend(int(t) for t in toks)
        tr.token_times.extend([now] * len(toks))
        if self.on_tokens is not None:
            self.on_tokens(now)

    def record_finish(self, uid, outcome):
        tr = self.trails.get(uid)
        if tr is None:
            return
        tr.t_done = self.clock()
        tr.outcome = outcome
        if self.on_finish is not None:
            self.on_finish(tr)

    def record_reject(self, uid, why):
        tr = self.trails.get(uid)
        if tr is not None:
            tr.outcome = "rejected"
            tr.t_done = self.clock()
            if self.on_finish is not None:
                self.on_finish(tr)

    def flush(self):
        if self.on_flush is not None:
            self.on_flush()

    # ------------------------------------------------------------ reads
    def measured(self):
        return [t for t in self.trails.values() if t.measured]

    def token_times(self):
        for tr in self.trails.values():
            yield from tr.token_times


# The backend compile event encloses the persistent-cache lookup, and a
# jit traced inside another jit's trace logs its own nested trace event.
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_PROGRAM_EVENT = _COMPILE_EVENTS[2]  # one per XLA program produced


class CompileClock:
    """Seconds JAX spends compiling inside a ``with`` block — tracing,
    lowering, and the backend compile or its persistent-cache retrieval —
    and the number of XLA programs produced, from JAX's monitoring
    events.  Events nest, so the seconds are the length of the union of
    their intervals, never more than the block's wall time."""

    def __enter__(self):
        self._spans = []
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, event, duration, **_):
        # the listener runs as the event ends
        if event in _COMPILE_EVENTS:
            end = time.perf_counter()
            self._spans.append((end - duration, end))
            self.programs += event == _PROGRAM_EVENT

    @property
    def seconds(self):
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            start = max(start, reach)
            if end > start:
                total += end - start
                reach = end
        return total
