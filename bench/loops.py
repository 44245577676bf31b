"""The traffic generator: a closed loop over the serving engine.

A ``Runner`` holds the engine, the clients and what each request did.  It
submits a client's next step, closes the open batch when it holds
``max_batch`` requests, and harvests batches in the order they closed,
once the engine says the batch's chunk is done on the device.  A
harvested client's state becomes the result the server returned, and its
next step is built from that.

Every call into the engine sits in a harness span (``bench/submit``,
``bench/poll``, ``bench/flush``); the harness's own client work in
``bench/client``.  Spans cost nothing unless the run traces.
"""
from __future__ import annotations

import gc
import time
from collections import defaultdict

import numpy as np

DRAIN_S = 60.0


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("owner", "name", "ann", "t")

    def __init__(self, owner, name):
        self.owner, self.name = owner, name

    def __enter__(self):
        self.ann = self.owner.annotation(self.name)
        self.ann.__enter__()
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t
        self.ann.__exit__(*exc)
        self.owner.total[self.name] += dt
        self.owner.count[self.name] += 1
        return False


class Spans:
    """Harness spans: a profiler annotation plus host-clock totals."""

    def __init__(self, on: bool):
        self.on = on
        self.total: dict = defaultdict(float)
        self.count: dict = defaultdict(int)
        if on:
            from jax.profiler import TraceAnnotation

            self.annotation = TraceAnnotation

    def __call__(self, name: str):
        return _Span(self, name) if self.on else _NO_SPAN

    def reset(self) -> None:
        self.total.clear()
        self.count.clear()


class Runner:
    """One engine, its clients, and the record of every request."""

    def __init__(self, engine, clients, kind: str, max_batch: int,
                 sample, spans: Spans, tick=None):
        self.engine, self.clients, self.kind = engine, clients, kind
        self.max_batch = max_batch
        self.spans = spans
        self.tick = tick if tick is not None else (lambda now: None)
        n = clients.count
        self.steps = np.zeros(n, np.int64)
        self.state: list = [None] * n
        self.chains = {int(c): [] for c in sample}
        self.open: dict = {}          # cycle -> [(client, ticket, t_ref, t_sub)]
        self.outstanding = 0
        self.done: list = []          # (client, t_ref, t_sub, t_done)
        self.failed: list = []        # (client, t_ref, error)

    # ----------------------------------------------------------- requests
    def submit(self, c: int, t_ref: float) -> None:
        with self.spans("bench/client"):
            args = self.clients.request(c, int(self.steps[c]) + 1,
                                        self.state[c])
        t = time.perf_counter()
        with self.spans("bench/submit"):
            ticket = self.engine.submit(self.kind, *args)
        self.open.setdefault(ticket.cycle, []).append((c, ticket, t_ref, t))
        self.outstanding += 1
        if self.engine.pending() >= self.max_batch:
            self.flush()

    def flush(self) -> None:
        with self.spans("bench/flush"):
            self.engine.flush()

    def harvest(self, most: int | None = 1) -> list:
        """Poll the engine, then take the oldest closed batches whose chunk
        is done, at most ``most`` of them (None: all).  Taking one at a time
        lets the loop resubmit a batch's clients before it harvests the
        next, so that completions spread over the window as they happen.
        Returns the clients served."""
        with self.spans("bench/poll"):
            self.engine.poll()
        served = []
        while self.open and (most is None or most > 0):
            if most is not None:
                most -= 1
            cycle = next(iter(self.open))
            entries = self.open[cycle]
            if self.engine.done_at(entries[0][1]) is None:
                break
            t = time.perf_counter()
            del self.open[cycle]
            self.outstanding -= len(entries)
            with self.spans("bench/client"):
                for c, ticket, t_ref, t_sub in entries:
                    try:
                        out = self.engine.result(ticket)
                    except Exception as e:  # noqa: BLE001 - a failed request
                        self.failed.append((c, t_ref, f"{type(e).__name__}: {e}"))
                        continue
                    self.state[c] = out
                    self.steps[c] += 1
                    chain = self.chains.get(c)
                    if chain is not None:
                        chain.append(out)
                    self.done.append((c, t_ref, t_sub, t))
                    served.append(c)
        return served

    def only_open_batch_left(self) -> bool:
        """True when every outstanding request waits in the open batch."""
        return self.outstanding > 0 and self.engine.pending() == self.outstanding

    def drain(self) -> None:
        """Close the open batch and harvest until nothing is outstanding,
        for at most ``DRAIN_S`` seconds."""
        if self.engine.pending():
            self.flush()
        end = time.perf_counter() + DRAIN_S
        while self.outstanding and time.perf_counter() < end:
            self.harvest(None)


def closed_loop(run: Runner, warm_steps: int, seconds: float) -> tuple:
    """Every client submits its next step as soon as its last is served.

    The window opens once every client has been served ``warm_steps``
    times and lasts ``seconds``; then submitting stops and the loop
    drains.  Objects made in set-up are collected and frozen as the window
    opens, so that the collector's full passes in the window walk only
    what the window makes.  Returns ``(t0, t_end)``.
    """
    n = run.clients.count
    t = time.perf_counter()
    for c in range(n):
        run.submit(c, t)
    warmed = 0
    t0 = t_end = None
    while True:
        served = run.harvest()
        now = time.perf_counter()
        if t0 is None:
            warmed += sum(1 for c in served if run.steps[c] == warm_steps)
            if warmed == n:
                gc.collect()
                gc.freeze()
                now = time.perf_counter()
                t0, t_end = now, now + seconds
                run.spans.reset()
        if t0 is not None:
            run.tick(now)
            if now >= t_end:
                break
        for c in served:
            run.submit(c, now)
        if run.only_open_batch_left():
            run.flush()
    run.drain()
    gc.unfreeze()
    return t0, t_end
