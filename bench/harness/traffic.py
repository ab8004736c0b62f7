"""The load generator: a closed loop of clients.

``clients`` coroutines drive the program's scheduler through ``submit``;
each sends its next query when its last one resolves, until the window
closes, and records, for every request, when it was sent and when its
future resolved, as the client sees it on the event loop.  After the
window the clients stop sending and wait, up to ``grace_s``, for every
request sent in the window to resolve.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int                     # position in the query pool
    sent: float                    # host clock
    done: Optional[float] = None
    outcome: object = None


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    requests: List[Request]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def answered(self) -> List[Request]:
        return [r for r in self.requests if r.done is not None]

    def latencies_ms(self) -> np.ndarray:
        return np.array([(r.done - r.sent) * 1e3 for r in self.answered()])


def _track(req: Request, fut) -> None:
    def done(f):
        req.done = time.perf_counter()
        req.outcome = f.exception() if f.exception() is not None \
            else f.result()
    fut.add_done_callback(done)


async def _settle(futures, grace_s: float) -> None:
    pending = [f for f in futures if not f.done()]
    if pending:
        await asyncio.wait(pending, timeout=grace_s)


async def closed_loop(sched, make: Callable[[int], object], clients: int,
                      seconds: float, start_index: int = 0,
                      grace_s: float = 60.0) -> Window:
    reqs: List[Request] = []
    futures = []
    counter = [start_index]
    t0 = time.perf_counter()
    t_end = t0 + seconds

    async def client():
        while time.perf_counter() < t_end:
            i = counter[0]
            counter[0] += 1
            now = time.perf_counter()
            req = Request(index=i, sent=now)
            reqs.append(req)
            fut = sched.submit(make(i))
            _track(req, fut)
            futures.append(fut)
            try:
                await asyncio.wait_for(asyncio.shield(fut),
                                       timeout=t_end + grace_s - now)
            except asyncio.TimeoutError:
                return
            except Exception:          # a failed request: the next one
                pass

    await asyncio.gather(*(client() for _ in range(clients)))
    await _settle(futures, grace_s)
    return Window(t0, t_end, reqs)

