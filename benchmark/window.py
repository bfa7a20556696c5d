"""The reader and the event handler that drive ``SGD.train``: warm steps
(the first three are the ones the check of outputs reads), then the
timed window, then the end of the pass.

The handler timestamps every ``EndIteration`` (the trainer reads the
cost on the host each step, so each is a finished step). The window
opens at the ``EndIteration`` of the last warm step and closes at the
first one at or after ``seconds`` later; the reader stops feeding then,
and what the prefetch thread had in flight still trains but is not
counted.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional

import jax
import jax.monitoring

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Window:
    def __init__(self, program, batches, *, seconds: float, warm_steps: int,
                 recorder=None, on_open: Optional[Callable] = None,
                 on_close: Optional[Callable] = None):
        self.program, self.batches = program, batches
        self.seconds, self.warm_steps = float(seconds), int(warm_steps)
        self.recorder = recorder
        self.on_open, self.on_close = on_open, on_close
        self.done = False
        self.first_end: Optional[float] = None   # first EndIteration
        self.opened: Optional[float] = None
        self.closed: Optional[float] = None
        self.ends: List[float] = []        # EndIteration times in the window
        self.costs: List[float] = []       # every step's cost, warm ones too
        self.at_open = self.at_close = None
        self.compile_events = 0            # backend compiles in the window
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT and self.opened is not None \
                and self.closed is None:
            self.compile_events += 1

    def reader(self):
        index = 0
        while not self.done:
            with jax.profiler.TraceAnnotation("bench_reader"):
                rows = self.program.rows(self.batches.at(index))
            yield rows
            index += 1

    def on_event(self, event) -> None:
        if not isinstance(event, self.program.events.EndIteration):
            return
        with jax.profiler.TraceAnnotation("bench_event_handler"):
            now = time.perf_counter()
            index = len(self.costs)
            if index == 0:
                self.first_end = now
            self.costs.append(float(event.cost))
            if self.recorder is not None:
                self.recorder.after_step(index, event.cost)
            if self.closed is not None:
                return
            if self.opened is None:
                if index + 1 >= self.warm_steps:
                    self.at_open = self.program.breakdown()
                    if self.on_open is not None:
                        self.on_open()
                    self.opened = time.perf_counter()
                return
            self.ends.append(now)
            if now - self.opened >= self.seconds:
                self.closed = now
                self.at_close = self.program.breakdown()
                self.done = True
                if self.on_close is not None:
                    self.on_close()

    def run(self) -> None:
        try:
            self.program.train(self.reader, self.on_event)
        finally:
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration)
        if self.closed is None:
            raise RuntimeError("the pass ended before the window closed")

    # -------------------------------------------------------- readings
    @property
    def steps(self) -> int:
        return len(self.ends)

    @property
    def window_s(self) -> float:
        return self.closed - self.opened

    def host_ms_per_step(self, *parts: str) -> Optional[float]:
        """Milliseconds a step of the window spent in these parts of the
        trainer's own ``StepBreakdown``; None where no step finished."""
        a, b = self.at_open, self.at_close
        steps = b["steps"] - a["steps"]
        if steps <= 0:
            return None
        return 1e3 * sum(b[p] - a[p] for p in parts) / steps

    def step_times(self) -> List[float]:
        """Seconds between consecutive ``EndIteration`` events, one for
        every step of the window (the first from the window's opening,
        which is the last warm step's event)."""
        starts = [self.opened] + self.ends[:-1]
        return [end - start for start, end in zip(starts, self.ends)]
