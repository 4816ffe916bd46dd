"""The measured window: the loops that drive `ACAMService.submit` and
`ACAMService.step` for one traffic mix, with the benchmark's own timers
around each call into the service.

What the window produced is kept in flat arrays (`Record`), not as the
service's response objects: a window holds some hundred thousand answers,
and keeping their objects alive would make each of Python's full garbage
collections, and so the latency tail, longer than the service alone makes
them.

In a traced run the loops also mark their own spans in the profiler trace
(``bench.submit``, ``bench.step``, ``bench.wait``), so that the device's
idle gaps can be put down to what the host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from array import array

import numpy as np

#: a request not answered this long after the window closed is missing
GRACE_S = 60.0

_NULL = contextlib.nullcontext()


def spans(trace: bool):
    """``spans(trace)(name)``: a profiler annotation in a traced run, else
    nothing."""
    if not trace:
        return lambda name: _NULL
    import jax

    return jax.profiler.TraceAnnotation


class Record:
    """Every answer and every tick of a run, in arrays.

    Answers: request id, time (s since the window opened), served pred,
    margin, escalation bit, and whether it came without an error. Ticks:
    per served slot the request id, the kernel's tenant-local winner and the
    tenant's index; per tick the number of served slots."""

    def __init__(self, tenant_index: dict):
        self.tenant_index = tenant_index
        self.clear()

    def clear(self) -> None:
        self.rid, self.pred = array("q"), array("q")
        self.t, self.margin = array("d"), array("d")
        self.esc, self.ok = array("b"), array("b")
        self.slot_rid, self.slot_win = array("q"), array("q")
        self.slot_tenant, self.tick_fill = array("q"), array("q")

    def add_answers(self, t: float, out: list) -> None:
        self.rid.extend([r.request_id for r in out])
        self.t.extend([t] * len(out))
        self.pred.extend([r.pred for r in out])
        self.margin.extend([r.margin for r in out])
        self.esc.extend([r.escalated for r in out])
        self.ok.extend([r.error is None for r in out])

    def add_tick(self, out: list) -> None:
        ok = [sr for sr in out if sr.error is None]
        self.slot_rid.extend([sr.item.request_id for sr in ok])
        self.slot_win.extend([sr.pred_local for sr in ok])
        index = self.tenant_index
        self.slot_tenant.extend([index[sr.item.tenant_id] for sr in ok])
        self.tick_fill.append(len(ok))

    def tick_tenants(self, first: int, last: int) -> list:
        """Tenant indices of the served slots of ticks [first, last)."""
        fill = np.frombuffer(self.tick_fill, np.int64)
        ends = np.cumsum(fill)
        ten = np.frombuffer(self.slot_tenant, np.int64)
        return [ten[e - f:e].tolist() for f, e in zip(fill[first:last],
                                                      ends[first:last])]


def record_ticks(svc, rec: Record) -> None:
    """Record every tick's scheduler results (the kernel's own winner,
    before the cascade): wraps `MicroBatchScheduler.tick`."""
    tick = svc.scheduler.tick

    def recorded():
        out = tick()
        rec.add_tick(out)
        return out

    svc.scheduler.tick = recorded


@dataclasses.dataclass
class Window:
    """What one measured window did. Request ``rid0 + i`` is the i-th
    request sent, pool entry ``i % len(pool)``."""

    kind: str
    rid0: int
    sent: int  # requests sent, in the window and after it
    population: int  # requests the end-to-end metrics count
    seconds: float  # length of the window, host clock
    record: Record
    tick0: int  # first tick of the window
    ticks_in_window: int
    submit_s: float = 0.0  # host time inside submit(), in the window
    submit_n: int = 0
    step_s: float = 0.0  # host time inside step() calls that dispatched
    step_n: int = 0
    due: np.ndarray | None = None  # open loop: due time of each request
    late: np.ndarray | None = None  # open loop: send time minus due time
    steps: np.ndarray | None = None  # (start, length) of each step

    def by_request(self, values, fill) -> np.ndarray:
        """An answer array indexed by request id - rid0 over the
        population; ``fill`` where a request has no answer without error."""
        rec = self.record
        i = np.frombuffer(rec.rid, np.int64) - self.rid0
        keep = (i >= 0) & (i < self.population) & \
            np.frombuffer(rec.ok, np.int8).astype(bool)
        v = np.asarray(values)
        out = np.full(self.population, fill, dtype=np.result_type(v, fill))
        out[i[keep]] = v[keep]
        return out

    def latencies_s(self) -> np.ndarray:
        """Open loop: for each request due in the window, its answer's time
        minus its due time. A request never answered counts as answered
        when the loop stopped."""
        t = np.frombuffer(self.record.t, np.float64)
        stop = max(float(t.max()) if len(t) else 0.0, self.seconds)
        return self.by_request(t, stop) - self.due


def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least a share
    ``q`` of the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(int(np.ceil(q * len(v))) - 1, 0)])


def closed_loop(svc, reqs: list, rec: Record, *, depth: int,
                seconds: float, trace: bool) -> Window:
    """MLPerf "Offline": top the queue up to ``depth`` before every step,
    so that every tick is full; runs until ``seconds`` have passed. What is
    still queued at the close is the caller's to drain (not counted)."""
    span = spans(trace)
    sched = svc.scheduler
    pool = len(reqs)
    i = answered = steps = 0
    submit_s = step_s = 0.0
    step_times = array("d")
    tick0 = len(rec.tick_fill)
    rid0 = None
    t0 = time.perf_counter()
    end = t0 + seconds
    c = t0
    while c < end:
        a = time.perf_counter()
        with span("bench.submit"):
            while sched.qsize < depth:
                rid = svc.submit(reqs[i % pool])
                rid0 = rid if rid0 is None else rid0
                i += 1
        b = time.perf_counter()
        with span("bench.step"):
            out = svc.step()
        c = time.perf_counter()
        submit_s += b - a
        step_s += c - b
        step_times.extend((b - t0, c - b))
        steps += 1
        answered += len(out)
        rec.add_answers(c - t0, out)
    win = Window(kind="closed", rid0=rid0, sent=i, population=answered,
                 seconds=c - t0, record=rec, tick0=tick0,
                 ticks_in_window=len(rec.tick_fill) - tick0,
                 submit_s=submit_s, submit_n=i, step_s=step_s,
                 step_n=steps,
                 steps=np.frombuffer(step_times).reshape(-1, 2))
    return win


def open_loop(svc, reqs: list, rec: Record, *, due: np.ndarray,
              seconds: float, trace: bool) -> Window:
    """MLPerf "Server": send each request at its due time (one thread:
    send everything due, step while the queue holds work, else wait for
    the next due time); stops once every request due in the window has
    its answer, or ``GRACE_S`` after the close."""
    span = spans(trace)
    sched = svc.scheduler
    pool = len(reqs)
    n = len(due)
    late = np.zeros(n)
    j = 0
    submit_s = step_s = 0.0
    step_n = 0
    step_times = array("d")
    tick0 = n_win_ticks = len(rec.tick_fill)
    rid0 = None
    perf = time.perf_counter
    t0 = perf()
    while True:
        now = perf() - t0
        if j < n and due[j] <= now:
            with span("bench.submit"):
                while j < n and due[j] <= now:
                    rid = svc.submit(reqs[j % pool])
                    rid0 = rid if rid0 is None else rid0
                    late[j] = perf() - t0 - due[j]
                    j += 1
            submit_s += perf() - t0 - now
        if sched.qsize:
            b = perf()
            with span("bench.step"):
                out = svc.step()
            c = perf()
            rec.add_answers(c - t0, out)
            step_times.extend((b - t0, c - b))
            if c - t0 <= seconds:
                step_s += c - b
                step_n += 1
                n_win_ticks = len(rec.tick_fill)
            continue
        if j == n or now > seconds + GRACE_S:
            break
        with span("bench.wait"):
            wait = due[j] - (perf() - t0)
            if wait > 2e-3:
                time.sleep(wait - 1e-3)
            while perf() - t0 < due[j]:
                pass
    return Window(kind="poisson", rid0=rid0, sent=j, population=n,
                  seconds=seconds, record=rec, tick0=tick0,
                  ticks_in_window=n_win_ticks - tick0,
                  submit_s=submit_s, submit_n=j, step_s=step_s,
                  step_n=step_n, due=due, late=late,
                  steps=np.frombuffer(step_times).reshape(-1, 2))
