"""The load driver: submits a schedule through ``EngineDriver`` and keeps
the client's clock.

Each request is stamped on the benchmark's side: when it was due, when it
was submitted, and when each of its tokens was handed to its ``subscribe``
callback (the driver hands over up to ``decode_chunk`` tokens per engine
step at once). All stamps are ``time.perf_counter`` seconds, the clock the
engine itself stamps with, so they compare with its spans.
"""

from __future__ import annotations

import dataclasses
import queue
import time
from typing import Callable, List, Optional

import numpy as np

from harness.traffic import Request

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    req: Request
    due: Optional[float] = None          # absolute, open loop only
    submit: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    handle: object = None
    finish: Optional[str] = None
    error: Optional[str] = None
    # filled by finalize(), once the driver is closed
    uid: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0                # the driver's stamps
    t_admit: float = 0.0

    def finalize(self) -> None:
        """Copy what the handle holds and let go of it (a handle keeps
        its driver, and so the engine's device state, alive)."""
        h = self.handle
        self.uid, self.tokens = h.uid, list(h.output)
        self.t_submit, self.t_admit = h.t_submit, h.t_admit
        self.handle = None


class Load:
    """Submits requests through a started ``EngineDriver`` and records
    every token's arrival."""

    def __init__(self, driver, sampling_params: Callable):
        self.driver = driver
        self.params = sampling_params
        self.records: List[Record] = []
        self.done: "queue.Queue[Record]" = queue.Queue()

    def submit(self, req: Request, due: Optional[float] = None) -> Record:
        rec = Record(req, due=due)
        self.records.append(rec)
        rec.submit = clock()
        rec.handle = self.driver.submit(req.prompt, self.params(req))

        def on_event(ev, rec=rec):
            # runs on the driver thread: stamp and return
            if ev[0] == "token":
                rec.times.append(clock())
            else:
                res = ev[1]
                rec.finish, rec.error = res.finish_reason, res.error
                self.done.put(rec)

        rec.handle.subscribe(on_event)
        return rec

    def resident(self) -> int:
        return self.driver.call(
            lambda e: sum(1 for s in e.slots if s is not None))


def sleep_until(t: float) -> None:
    while True:
        d = t - clock()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def run_open_loop(load: Load, reqs: List[Request], t_open: float,
                  seconds: float) -> float:
    """Submit each request at ``t_open + due_s`` until the window closes.
    Returns the close time."""
    t_close = t_open + seconds
    for req in sorted(reqs, key=lambda r: r.due_s):
        due = t_open + req.due_s
        if due >= t_close:
            break
        sleep_until(due)
        load.submit(req, due=due)
    sleep_until(t_close)
    return t_close


def fill_backlog(load: Load, reqs: List[Request], slots: int,
                 backlog: int, timeout: float = 600.0) -> int:
    """Submit the first ``slots + backlog`` requests and wait until every
    slot is occupied. Returns how many were submitted."""
    n = min(len(reqs), slots + backlog)
    for req in reqs[:n]:
        load.submit(req)
    t_end = clock() + timeout
    while load.resident() < slots:
        if clock() > t_end:
            raise TimeoutError(f"slots not filled after {timeout} s")
        time.sleep(0.02)
    return n


def run_backlog(load: Load, reqs: List[Request], next_i: int, t_open: float,
                seconds: float) -> float:
    """Replace each finished request at once until the window closes."""
    t_close = t_open + seconds
    while True:
        now = clock()
        if now >= t_close:
            break
        try:
            load.done.get(timeout=min(t_close - now, 0.05))
        except queue.Empty:
            continue
        if next_i >= len(reqs):
            raise RuntimeError("the backlog ran dry: raise 'requests' in "
                               "the traffic file")
        load.submit(reqs[next_i])
        next_i += 1
    return t_close


def wait_finished(records: List[Record], timeout: float) -> None:
    t_end = clock() + timeout
    for rec in records:
        while rec.finish is None and clock() < t_end:
            time.sleep(0.02)


def lateness(records: List[Record]) -> np.ndarray:
    """Seconds each open-loop request was submitted after it was due."""
    return np.asarray([r.submit - r.due for r in records
                       if r.due is not None], np.float64)
