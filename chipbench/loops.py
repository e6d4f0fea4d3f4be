"""The client: drives the program's own entry (``ServeEngine.submit`` /
``ServeEngine.step``) with generated requests, and times every token on
the host as ``step()`` hands it back.

Open loop: each request is submitted once it is due, whether or not
earlier ones have finished, and its latencies count from when it was
due.  Closed loop: a fixed number of requests is kept in flight.

Each engine call and each wait for an arrival is a profiler annotation
(``client.step``, ``client.wait``), so a trace shows what the host did
while the device was idle.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator

import jax

from chipbench.gen import Req


@dataclasses.dataclass
class Log:
    """What the client saw of one request."""

    req: Req
    due: float                  # absolute host time it was due
    submit: float = 0.0
    times: list = dataclasses.field(default_factory=list)   # per token
    tokens: list = dataclasses.field(default_factory=list)
    queue_wait_s: float | None = None   # the engine's submit -> admission

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.req.max_new_tokens


def _request(req: Req):
    from repro.serve import Request
    return Request(rid=req.rid, prompt=req.prompt.tolist(),
                   max_new_tokens=req.max_new_tokens)


def _step(engine, logs: dict, clock: Callable[[], float]) -> list[int]:
    """One engine step; returns the rids it finished."""
    with jax.profiler.TraceAnnotation("client.step"):
        events = engine.step()
    t = clock()
    for rid, tok in events:
        log = logs[rid]
        log.times.append(t)
        log.tokens.append(int(tok))
    finished = engine.pop_results()
    for rid, res in finished.items():
        logs[rid].queue_wait_s = res.queue_wait_s
    return list(finished)


def open_loop(engine, schedule: list[Req], t0: float, drain_until: float,
              clock: Callable[[], float] = time.perf_counter) -> dict:
    """Submit each request at ``t0 + due``; step while work is in
    flight; stop once every request is done or at ``drain_until``."""
    logs = {r.rid: Log(req=r, due=t0 + r.due) for r in schedule}
    order = sorted(logs.values(), key=lambda g: g.due)
    i, n, left = 0, len(order), len(order)
    while left > 0:
        now = clock()
        if now >= drain_until:
            break
        while i < n and order[i].due <= now:
            engine.submit(_request(order[i].req))
            order[i].submit = clock()
            i += 1
        if engine.idle:
            if i < n:
                with jax.profiler.TraceAnnotation("client.wait"):
                    time.sleep(max(0.0, order[i].due - clock()))
            continue
        left -= len(_step(engine, logs, clock))
    return logs


def closed_loop(engine, stream: Iterator[Req], outstanding: int,
                t_close: float,
                clock: Callable[[], float] = time.perf_counter) -> dict:
    """Keep ``outstanding`` requests in flight until the first step
    that returns at or after ``t_close``."""
    logs: dict = {}
    in_flight = 0
    while True:
        while in_flight < outstanding:
            req = next(stream)
            log = Log(req=req, due=clock())
            engine.submit(_request(req))
            log.submit = log.due
            logs[req.rid] = log
            in_flight += 1
        in_flight -= len(_step(engine, logs, clock))
        if clock() >= t_close:
            return logs
