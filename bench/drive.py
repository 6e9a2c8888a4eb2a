"""Drive the service through a schedule and record every request on the
client's own clock.

A request's latency runs from when it was sent (closed loop) or due (open
loop) to when its answer reached the client.  The window opens when the
first request is due and closes when the last request sent or due before
``seconds`` has been answered, so the count of answers has no step at the
end.  An answer that has not come a minute past the close is missing.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future

import jax

LATE_WAIT_S = 60.0


@dataclasses.dataclass
class Request:
    queries: tuple[str, ...]
    due: float                       # seconds after the window opened
    sent: float = float("nan")
    done: float = float("nan")
    # one entry per query: a QueryResult, or the exception it raised
    results: list = dataclasses.field(default_factory=list)

    @property
    def latency(self) -> float:
        return self.done - self.due


@dataclasses.dataclass
class Window:
    requests: list[Request]
    close: float                     # seconds from open to the last answer

    @property
    def answered(self) -> list[Request]:
        return [r for r in self.requests if r.done == r.done]


def _call(svc, call: str, sqls: list[str]) -> list:
    """One request through ``call``, to its answers on the host."""
    if call == "submit_many":
        return svc.submit_many(sqls)
    if call == "submit":
        out = []
        for s in sqls:
            try:
                out.append(svc.submit(s))
            except Exception as e:        # recorded as a failed answer
                out.append(e)
        return out
    futs = [svc.submit_async(s) for s in sqls]
    out = []
    for f in futs:
        try:
            out.append(f.result(timeout=LATE_WAIT_S))
        except Exception as e:
            out.append(e)
    return out


def warm(svc, call: str, batches: list[list[str]]) -> None:
    """Run every batch once, through the mix's own entry, so each program
    the window can use is compiled (or loaded from the compile cache) and
    has run; an async entry also starts the service's batcher."""
    for sqls in batches:
        with jax.profiler.TraceAnnotation("bench.warm"):
            if call == "submit_async":
                # one window, so the batcher sees the whole subset at once
                _call(svc, "submit_many", sqls)
                _call(svc, "submit_async", sqls[:1])
            else:
                _call(svc, call, sqls)


def closed_loop(svc, call: str, sql: dict[str, str],
                client_requests: list[list[tuple[str, ...]]],
                seconds: float) -> Window:
    """Each client sends its next request as soon as the last is answered,
    while the window is younger than ``seconds``."""
    t_open = time.perf_counter()
    per_client: list[list[Request]] = [[] for _ in client_requests]

    def client(i: int) -> None:
        reqs = client_requests[i]
        k = 0
        while True:
            sent = time.perf_counter() - t_open
            if sent >= seconds:
                return
            q = reqs[k % len(reqs)]
            k += 1
            r = Request(q, due=sent, sent=sent)
            with jax.profiler.TraceAnnotation("bench.request"):
                r.results = _call(svc, call, [sql[n] for n in q])
            r.done = time.perf_counter() - t_open
            per_client[i].append(r)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(client_requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    reqs = sorted((r for rs in per_client for r in rs), key=lambda r: r.due)
    return Window(reqs, max(r.done for r in reqs))


def open_loop(svc, sql: dict[str, str],
              arrivals: list[tuple[float, tuple[str, ...]]],
              seconds: float) -> Window:
    """Send each arrival through ``submit_async`` when it is due, however
    far behind the service is; answers are timed when their future
    resolves."""
    reqs = [Request(q, due=t) for t, q in arrivals if t < seconds]
    futs: list[list[Future]] = []
    pending = threading.Semaphore(0)
    t_open = time.perf_counter()

    lock = threading.Lock()

    def on_done(r: Request, left: list[int]):
        # the request is answered when its last query is
        def cb(_f):
            with lock:
                left[0] -= 1
                if left[0]:
                    return
                r.done = time.perf_counter() - t_open
            pending.release()
        return cb

    for r in reqs:
        wait = r.due - (time.perf_counter() - t_open)
        if wait > 0:
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(wait)
        r.sent = time.perf_counter() - t_open
        with jax.profiler.TraceAnnotation("bench.submit_async"):
            fs = []
            for name in r.queries:
                try:
                    fs.append(svc.submit_async(sql[name]))
                except Exception as e:     # refused at the door
                    f = Future()
                    f.set_exception(e)
                    fs.append(f)
            futs.append(fs)
            cb = on_done(r, [len(fs)])
            for f in fs:
                f.add_done_callback(cb)
    deadline = t_open + seconds + LATE_WAIT_S
    with jax.profiler.TraceAnnotation("bench.drain"):
        for _ in reqs:
            if not pending.acquire(timeout=max(0.0, deadline
                                               - time.perf_counter())):
                break
    for r, fs in zip(reqs, futs):
        for f in fs:
            if not f.done():
                r.results.append(TimeoutError("no answer a minute past "
                                              "the close"))
                continue
            try:
                r.results.append(f.result())
            except Exception as e:
                r.results.append(e)
    done = [r.done for r in reqs if r.done == r.done]
    return Window(reqs, max(done) if done else float("nan"))
