"""The serving loop that the measured window drives, on the wall clock.

``ContinuousBatchingScheduler.run`` is an event loop on virtual time, so
the loop lives here, built on the engine's public session API in the
scheduler's order (``_on_arrival``, ``_drain_prefill_interleaved``,
``_on_end``).  Each iteration:

1. every arrival now due is routed (``OnlineBPRR.admit``) and registered
   (``create_session``); every waiting session is offered the engine's
   slots in FIFO order (``try_admit_sessions``);
2. one ``prefill_round`` if a prefill is pending;
3. one ``decode_round``;
4. finished sessions retire (``retire_session``, ``OnlineBPRR.finish``).

For the requests the check samples (``watch``), the logits behind each
served token are kept as the program left them (``Session.last_logits``,
a row of the round's logits on the device): one small indexing dispatch
per watched session per round, and no host sync.

Every call into the program is a host span, kept in memory and also
written as a ``jax.profiler.TraceAnnotation`` so that a traced run can
attribute device idle time to what the host was doing.
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from bench.stats import Record


@dataclass
class LoopResult:
    records: List[Record]
    spans: List[Tuple[str, float, float]]  # (name, start, end), window s
    rounds: List[Tuple[float, List[int]]]  # (start, positions decoded)
    prefills: List[Tuple[float, int]]  # (start, sessions completed)
    lateness: List[float] = field(default_factory=list)
    window_closed: Optional[float] = None
    t0: float = 0.0  # ``time.perf_counter()`` at the window's start


class _Clock:
    def __init__(self):
        self.t0 = time.perf_counter()
        self.spans: List[Tuple[str, float, float]] = []

    def now(self) -> float:
        return time.perf_counter() - self.t0

    @contextmanager
    def span(self, name: str):
        start = self.now()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, start, self.now()))


def drive(system, ctl, requests, mix: dict, seconds: float,
          drain_s: float, marks=(), watch=()) -> LoopResult:
    """Serve ``requests`` on the wall clock for a window of ``seconds``,
    then drain what is in flight for at most ``drain_s`` more.  The open
    loop offers every request at its due time; the closed loop keeps
    ``outstanding`` requests in flight, issuing the next from the cycled
    pool whenever one retires, until the window closes.  ``marks`` are
    (time, callable) pairs, each called once, between calls into the
    program, when the window's clock first reaches its time (a traced run
    starts and stops its profiler so).  ``watch`` holds the rids whose
    logits are kept; after the drain each watched record's ``logits`` is
    a (tokens, vocab) float32 array."""
    closed = mix["loop"] == "closed"
    clk = _Clock()
    watch = set(watch)
    marks = sorted(marks, key=lambda m: m[0])
    pools = [s.pool for s in system.servers.values()]
    records: List[Record] = []
    lateness: List[float] = []
    rounds: List[Tuple[float, List[int]]] = []
    prefills: List[Tuple[float, int]] = []
    if closed:
        counter = itertools.count()

        def issue(now):
            i = next(counter)
            return _Offer(i, now, requests[i % len(requests)])

        due_q = deque(issue(0.0) for _ in range(int(mix["outstanding"])))
    else:
        due_q = deque(_Offer(r.rid, r.due, r) for r in requests)
    waiting: List[Tuple[int, int, Record]] = []
    active: Dict[int, Tuple[int, Record]] = {}
    closed_at = None
    while True:
        now = clk.now()
        while marks and now >= marks[0][0]:
            marks.pop(0)[1]()
        if closed_at is None and now >= seconds:
            closed_at = now
        # 1. arrivals due, then admission in FIFO order
        while due_q and due_q[0].due <= now:
            off = due_q.popleft()
            rec = Record(rid=off.rid, due=off.due,
                         prompt_len=len(off.req.prompt),
                         n_new=off.req.n_new, seen=now,
                         prompt=off.req.prompt)
            records.append(rec)
            lateness.append(now - off.due)
            with clk.span("admit"):
                route, _, _, sid_ctl = ctl.admit(off.req.client, now)
                if route is not None:
                    sid = system.create_session(off.req.prompt,
                                                off.req.client, route,
                                                off.req.n_new)
            if route is None:
                rec.failed = "no_route"
                continue
            rec.route = tuple(route.servers)
            waiting.append((sid, sid_ctl, rec))
        if waiting:
            with clk.span("try_admit"):
                got = set(system.try_admit_sessions([w[0] for w in waiting]))
            if got:
                t = clk.now()
                for sid, sid_ctl, rec in waiting:
                    if sid in got:
                        rec.granted = t
                        active[sid] = (sid_ctl, rec)
                waiting = [w for w in waiting if w[0] not in got]
        # 2. one prefill round
        if system.has_pending_prefill():
            start = clk.now()
            with clk.span("prefill_round"):
                done = system.prefill_round()
                jax.block_until_ready([p.tree for p in pools])
            t = clk.now()
            prefills.append((start, len(done)))
            for sid in done:
                rec = active[sid][1]
                rec.token_times.append(t)
                rec.tokens.append(system.sessions[sid].tokens[-1])
                _keep(rec, system.sessions[sid], watch)
        # 3. one decode round
        dec = [sid for sid in active
               if system.sessions[sid].state == "active"
               and system.sessions[sid].n_generated
               < system.sessions[sid].n_new]
        if dec:
            positions = [system.sessions[sid].pos for sid in dec]
            start = clk.now()
            with clk.span("decode_round"):
                out = system.decode_round()
            t = clk.now()
            rounds.append((start, positions))
            for sid, tok in out.items():
                rec = active[sid][1]
                rec.token_times.append(t)
                rec.tokens.append(int(tok))
                _keep(rec, system.sessions[sid], watch)
        # 4. retire what finished or failed
        for sid in list(active):
            sess = system.sessions[sid]
            if sess.state == "failed" or sess.n_generated >= sess.n_new:
                sid_ctl, rec = active.pop(sid)
                with clk.span("retire"):
                    system.retire_session(sid)
                    ctl.finish(sid_ctl)
                if sess.state == "failed":
                    rec.failed = sess.fail_reason or "failed"
                if closed and clk.now() < seconds:
                    due_q.append(issue(clk.now()))
        busy = bool(active or waiting or system.has_pending_prefill())
        if not busy and not due_q:
            if closed_at is None and clk.now() < seconds:
                # nothing in flight and nothing left to offer: idle out
                with clk.span("generator_idle"):
                    time.sleep(max(0.0, seconds - clk.now()))
                continue
            break
        if clk.now() > seconds + drain_s:
            for _, _, rec in waiting:
                rec.failed = "not_admitted_by_drain_limit"
            for _, rec in active.values():
                rec.failed = "unfinished_at_drain_limit"
            break
        if not busy and due_q:
            with clk.span("generator_idle"):
                time.sleep(max(0.0, due_q[0].due - clk.now()))
    if closed_at is None:
        closed_at = clk.now()
    for _, fn in marks:
        fn()
    for rec in records:
        if rec.logits is not None:
            rec.logits = np.stack([np.asarray(x, np.float32).reshape(-1)
                                   for x in jax.device_get(rec.logits)])
    return LoopResult(records=records, spans=clk.spans, rounds=rounds,
                      prefills=prefills, lateness=lateness,
                      window_closed=closed_at, t0=clk.t0)


def _keep(rec: Record, sess, watch) -> None:
    if rec.rid in watch:
        if rec.logits is None:
            rec.logits = []
        rec.logits.append(sess.last_logits)


@dataclass
class _Offer:
    rid: int
    due: float
    req: object
