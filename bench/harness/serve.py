"""The measured window: a single-threaded server loop over the engine.

The loop is what a host serving images does with ``DiffusionEngine``:
it submits each request when it is due, calls ``step()`` whenever the
engine has work and every image already handed out by a ``Finished`` is
ready on the device, and delivers images as they become ready.  It
never blocks on the device: readiness is polled, so arrivals are
submitted while a batch runs.  Each request's record holds its due,
submit, dispatch and ready times (``time.perf_counter``).

With a tracer the loop marks what the host is doing with
``jax.profiler.TraceAnnotation`` spans on the profiler's clock:
``bench.submit``, ``bench.step``, ``bench.wait_device`` (a batch runs)
and ``bench.wait_arrival`` (nothing to run).
"""
from __future__ import annotations

import time

import jax

POLL_S = 0.0002
DRAIN_S = 60.0


class Spans:
    """One open host span at a time, switched as the loop's state
    changes; inert unless ``on``."""

    def __init__(self):
        self.on = False
        self._cur = None
        self._name = None

    def enter(self, name: str) -> None:
        if not self.on or name == self._name:
            return
        self.close()
        self._cur = jax.profiler.TraceAnnotation(name)
        self._cur.__enter__()
        self._name = name

    def close(self) -> None:
        if self._cur is not None:
            self._cur.__exit__(None, None, None)
        self._cur = self._name = None


class Window:
    """Drive ``engine`` with ``reqs`` for ``seconds``.

    ``due`` gives each request's due time from the window's start (open
    loop), or is ``None``: then the queue is topped up to ``queue``
    requests (closed loop) and the window starts at the first dispatch.
    ``tracer(now)`` is called at each batch boundary (no batch in
    flight) and may start or stop the profiler.
    """

    def __init__(self, engine, to_request, reqs, due, seconds, *,
                 queue: int = 0, tracer=None):
        self.engine = engine
        self.to_request = to_request
        self.reqs = reqs
        self.due = due
        self.seconds = seconds
        self.queue = queue
        self.tracer = tracer
        self.spans = Spans()
        self.rec: dict[int, dict] = {}
        self.batches: list[dict] = []
        self.images: dict[int, jax.Array] = {}
        self.failed = 0
        self._next = 0
        self._inflight: list = []

    # -------------------------------------------------------- pieces
    def _submit(self, r: dict, due: float) -> None:
        self.spans.enter("bench.submit")
        rec = {"due": due, "submit": time.perf_counter()}
        self.rec[r["rid"]] = rec
        try:
            self.engine.submit(self.to_request(r))
        except Exception as e:          # a refused request is a failure
            rec["error"] = repr(e)
            self.failed += 1

    def _arrivals(self, now: float) -> None:
        if self.due is None:
            while (len(self.engine.queue) < self.queue
                   and self._next < len(self.reqs)):
                self._submit(self.reqs[self._next], now)
                self._next += 1
            return
        while (self._next < len(self.reqs)
               and self.t0 + self.due[self._next] <= now):
            self._submit(self.reqs[self._next],
                         self.t0 + self.due[self._next])
            self._next += 1

    def _poll(self, now: float) -> None:
        still = []
        for res in self._inflight:
            if res.image.is_ready():
                self.rec[res.rid]["ready"] = now
                self.images[res.rid] = res.image
            else:
                still.append(res)
        self._inflight = still

    def _dispatch(self) -> None:
        self.spans.enter("bench.step")
        fin = self.engine.finished
        n0 = len(fin)
        t = time.perf_counter()
        self.engine.step()
        new = fin[n0:]
        del fin[n0:]                    # results are delivered from here
        for res in new:
            self.rec[res.rid]["dispatch"] = t
        self.batches.append({"dispatch": t, "rows": len(new),
                             "rids": [res.rid for res in new]})
        self._inflight.extend(new)

    def _wait(self, now: float, end: float) -> None:
        busy = bool(self._inflight)
        self.spans.enter("bench.wait_device" if busy
                         else "bench.wait_arrival")
        nxt = end
        if not busy and self.due is not None and self._next < len(self.due):
            nxt = min(nxt, self.t0 + self.due[self._next])
        time.sleep(max(0.0, min(POLL_S if busy else nxt - now, POLL_S * 5)))

    # ---------------------------------------------------------- run
    def run(self) -> None:
        self.t0 = time.perf_counter()
        if self.due is None:            # fill the queue, then start
            self._arrivals(self.t0)
            self.t0 = time.perf_counter()
        end = self.t0 + self.seconds
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            self._arrivals(now)
            now = time.perf_counter()
            if self._inflight:
                self._poll(now)
            if not self._inflight:
                if self.tracer is not None:
                    self.tracer(now)
                if self.engine.has_work():
                    self._dispatch()
                    continue
            self._wait(now, end)
        self.t_close = time.perf_counter()
        self.spans.close()

    def drain(self, open_loop: bool) -> None:
        """After the close: finish what is in flight and, for an open
        loop, everything that was due within the window."""
        limit = time.perf_counter() + DRAIN_S
        while time.perf_counter() < limit:
            now = time.perf_counter()
            if self._inflight:
                self._poll(now)
            if not self._inflight:
                if not open_loop or not self.engine.has_work():
                    break
                self._dispatch()
                continue
            time.sleep(POLL_S)
        self.spans.close()
        self.t_given_up = time.perf_counter()
        for res in self._inflight:
            self.rec[res.rid]["error"] = "never ready"
            self.failed += 1
