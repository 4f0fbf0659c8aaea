"""The load the benchmark offers, and the client log it keeps.

Requests go through the program's own front door (``FrontDoor.submit``) in
this process; a consumer coroutine per request stamps each event when it
receives it, on the benchmark's clock (``time.perf_counter``).  That is the
time a user of the system would see, and every end-to-end metric is taken
from it, never from the program's own timestamps.

Open loop: request ``i`` is due at ``traffic.arrival(i)``; a generator
sleeps until then and submits, and records how late it ran.  Closed loop:
``clients`` coroutines each send a request, read its stream to the end and
send the next.  Both run from the start of the ramp to the close of the
window.  After the close, the benchmark waits for the first token of every
request that was due (or sent) before it, up to ``WAIT_S``: a late answer
is late, and its wait counts.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

WAIT_S = 60.0


@dataclass
class Rec:
    """One request as the client saw it (times on the benchmark clock)."""
    i: int
    prompt: np.ndarray
    max_new: int
    due: float                     # open loop: schedule; closed: send time
    sent: float = 0.0
    times: List[float] = field(default_factory=list)   # token receipts
    tokens: List[int] = field(default_factory=list)
    state: Optional[str] = None    # the stream's terminal state
    error: Optional[str] = None    # refused at submit

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[-1])

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None

    @property
    def finished(self) -> bool:
        return self.state == "done" and len(self.tokens) == self.max_new


@dataclass
class Window:
    t0: float                      # benchmark clock at the start of the ramp
    open_at: float = float("nan")  # when the window opened (benchmark clock)
    close_at: float = float("nan")

    @property
    def seconds(self) -> float:
        return self.close_at - self.open_at


async def drive(fd, traffic, request_cls, *, ramp_s: float, seconds: float,
                on_open: Callable[[], None], on_close: Callable[[], None],
                clock=time.perf_counter):
    """Offer the mix to ``fd`` through ramp and window; returns
    ``(records, window)``.  ``on_open``/``on_close`` run at the window's
    edges (they start and stop the profiler in a traced run)."""
    t0 = clock()
    now = lambda: clock() - t0
    win = Window(t0=t0)
    recs: List[Rec] = []
    tasks: List[asyncio.Task] = []
    closed = asyncio.Event()

    async def consume(rec: Rec, stream) -> None:
        async for ev in stream:
            if ev.kind == "token":
                rec.times.append(now())
                rec.tokens.append(int(ev.token[0]))
            elif ev.kind == "done":
                rec.state = ev.state

    def send(i: int, due: float) -> Optional[asyncio.Task]:
        p_len, o_len = traffic.size(i)
        rec = Rec(i=i, prompt=traffic.prompt(i), max_new=o_len, due=due)
        recs.append(rec)
        rec.sent = now()
        try:
            stream = fd.submit(request_cls(rid=i, prompt=rec.prompt,
                                           max_new=o_len))
        except Exception as exc:            # Overloaded / ShuttingDown
            rec.error = f"{type(exc).__name__}: {exc}"
            return None
        t = asyncio.ensure_future(consume(rec, stream))
        tasks.append(t)
        return t

    async def timer() -> None:
        await asyncio.sleep(max(0.0, ramp_s - now()))
        win.open_at = now()
        on_open()
        await asyncio.sleep(max(0.0, win.open_at + seconds - now()))
        win.close_at = now()
        on_close()
        closed.set()

    async def open_loop() -> None:
        # every request due before the close is sent, however late
        i = 0
        while True:
            due = traffic.arrival(i)
            if closed.is_set() and due >= win.close_at:
                return
            if due > now() and not closed.is_set():
                try:
                    await asyncio.wait_for(closed.wait(), due - now())
                    continue
                except asyncio.TimeoutError:
                    pass
            send(i, due)
            i += 1

    counter = iter(range(10 ** 9))

    async def closed_client() -> None:
        while not closed.is_set():
            t = send(next(counter), now())
            if t is None:
                await asyncio.sleep(0.01)
            else:
                await t

    await fd.start()
    timer_task = asyncio.ensure_future(timer())
    if traffic.loop == "open":
        load = [asyncio.ensure_future(open_loop())]
    else:
        load = [asyncio.ensure_future(closed_client())
                for _ in range(traffic.clients)]
    await timer_task
    if traffic.loop == "open":
        await load[0]
    due = [r for r in recs if r.due < win.close_at and r.error is None]
    limit = now() + WAIT_S
    while now() < limit and any(r.first is None and r.state is None
                                for r in due):
        await asyncio.sleep(0.01)
    for t in tasks + load:
        t.cancel()
    await asyncio.gather(*tasks, *load, return_exceptions=True)
    await fd.aclose()
    return recs, win
