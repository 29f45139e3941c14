"""The closed-loop load driver: one thread per client, each submitting its
next request `think_s` after the last one has ended.

Clients start staggered. The timed window opens when every client has
completed one request (warm-up, not timed) and closes by the clock; what is
in flight then is cancelled. Every token's delivery time is stamped in the
engine's `on_token` callback with the host's monotonic clock.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field


@dataclass
class Record:
    """What the window knows about one request."""
    client: int
    index: int
    n_prompt: int
    max_tokens: int
    submit_t: float
    token_t: list = field(default_factory=list)
    end_t: float | None = None
    finish: str | None = None
    error: str | None = None
    cancelled_by_driver: bool = False


class Window:
    def __init__(self):
        self.open_t: float | None = None
        self.close_t: float | None = None


def run_closed(be, plans, sampler_factory, seconds: float, stagger_s: float,
               think_s: float = 0.0, at_open=None, at_close=None,
               request_timeout: float = 300.0):
    """Drive `be` with one thread per plan. Returns (records, window).
    `at_open`/`at_close` are called on the driver's thread at the window's
    edges (profiler and counter snapshots)."""
    records: list[Record] = []
    lock = threading.Lock()
    warm = threading.Semaphore(0)
    stop = threading.Event()
    live: dict[int, object] = {}

    def client(plan, delay):
        time.sleep(delay)
        first = True
        while not stop.is_set():
            r = plan.next()
            rec = Record(r.client, r.index, len(r.prompt), r.max_tokens,
                         time.perf_counter())
            with lock:
                records.append(rec)
            try:
                req = be.submit(list(r.prompt), r.max_tokens,
                                sampler_factory(),
                                on_token=lambda _t, rec=rec: rec.token_t.append(
                                    time.perf_counter()))
            except Exception as e:  # refused at admission: a failed request
                rec.end_t, rec.error = time.perf_counter(), repr(e)
                time.sleep(0.05)
            else:
                with lock:
                    live[r.client] = (req, rec)
                try:
                    req.wait(request_timeout)
                except Exception as e:
                    rec.error = repr(e)
                rec.end_t = time.perf_counter()
                rec.finish = req.finish
            if first:
                warm.release()
                first = False
            time.sleep(think_s)

    threads = [threading.Thread(target=client, args=(p, i * stagger_s),
                                daemon=True, name=f"client-{i}")
               for i, p in enumerate(plans)]
    for t in threads:
        t.start()
    for _ in threads:
        warm.acquire()
    w = Window()
    if at_open:
        at_open()
    w.open_t = time.perf_counter()
    time.sleep(seconds)
    w.close_t = time.perf_counter()
    if at_close:
        at_close()
    stop.set()
    with lock:
        for req, rec in live.values():
            if not req.done.is_set():
                rec.cancelled_by_driver = True
                req.cancel()
    for t in threads:
        t.join(request_timeout)
    return records, w
