"""The load generators, one per traffic kind, each driven by the
parameters of one traffic file and by the run's seed.

``sweep``: a closed loop of back-to-back ``Explorer.explore(block)`` calls
over ``blocks`` candidate blocks of ``block`` rows each, drawn from the
seed before the window and cycled.

``serve``: an open loop of queries through ``ServeClient`` ->
``ServeFrontend`` -> ``DSEService``.  ``rate_qps * seconds`` arrivals are
spread uniformly at random over the window (a Poisson stream conditioned
on its count, so every seed offers the same amount of work).  At
``new_share`` of the arrivals, the same number on every seed at seeded
positions, comes a new question (the next question of the seeded catalog
that has not been asked yet); every other arrival repeats an earlier one,
drawn by Zipf with exponent ``zipf_s`` over the order in which questions
were first asked.  Every query is timed from the moment it was
due, so a stall in the generator or the service delays the later queries
in the count.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def candidates(knobs: Sequence[Dict], n: int, rng: np.random.Generator
               ) -> np.ndarray:
    """(n, K) log-uniform samples of the knob box; row 0 is theta = 1, so
    every set carries the reference machine."""
    cols = [np.exp(rng.uniform(np.log(k["lo"]), np.log(k["hi"]), n))
            for k in knobs]
    out = np.stack(cols, axis=1).astype(np.float32)
    if n:
        out[0] = 1.0
    return out


def sweep_blocks(knobs: Sequence[Dict], traffic: Dict, seed: int
                 ) -> List[np.ndarray]:
    """The ``blocks`` candidate blocks a sweep cell cycles through."""
    b, nb = int(traffic["block"]), int(traffic["blocks"])
    cand = candidates(knobs, b * nb, rng_for(seed, 0))
    return [cand[i * b:(i + 1) * b] for i in range(nb)]


# -- serve traffic ------------------------------------------------------------


def override_levels(knob: Dict, n: int) -> List[float]:
    """``n`` log-spaced pinned values across a knob's box."""
    return [float(v) for v in np.geomspace(knob["lo"], knob["hi"], n)]


def catalog(cells: Sequence, knobs: Sequence[Dict], traffic: Dict,
            rng: np.random.Generator) -> List[Dict]:
    """Every question of the mix that resolves to at least one cell, as
    query payloads (``workload``, ``archs``, ``overrides``, ``top_k``), in
    a seeded order.  ``cells`` are (arch, workload) pairs in matrix order."""
    workloads = sorted({w for _, w in cells})
    archs = sorted({a for a, _ in cells})
    pins = [{}] + [{k["name"]: v} for k in knobs
                   for v in override_levels(k, int(traffic["pin_levels"]))]
    out = []
    for w, a in itertools.product([None] + workloads, [None] + archs):
        if not any((w is None or cw == w) and (a is None or ca == a)
                   for ca, cw in cells):
            continue
        for pin, k in itertools.product(pins, traffic["top_k"]):
            out.append({"workload": w, "archs": None if a is None else [a],
                        "overrides": pin, "top_k": int(k)})
    order = rng.permutation(len(out))
    return [out[i] for i in order]


@dataclass
class Schedule:
    """The questions set-up asks, and the window's arrivals."""

    warm: List[Dict]           # asked untimed in set-up, in order
    due: np.ndarray            # (N,) seconds after the window opens
    questions: List[Dict]      # (N,) the question of each arrival
    new: np.ndarray            # (N,) bool, first time asked


def schedule(cat: List[Dict], traffic: Dict, seconds: float,
             rng: np.random.Generator) -> Schedule:
    warm_n = int(traffic["warm_questions"])
    asked = list(cat[:warm_n])
    nxt = warm_n
    n = int(round(float(traffic["rate_qps"]) * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    s = float(traffic["zipf_s"])
    # the same number of new questions on every seed, at seeded positions
    fresh = np.zeros(n, bool)
    fresh[rng.permutation(n)[:int(round(float(traffic["new_share"]) * n))]] \
        = True
    questions, new = [], np.zeros(n, bool)
    for k in range(n):
        if fresh[k] and nxt < len(cat):
            q = cat[nxt]
            nxt += 1
            asked.append(q)
            new[k] = True
        else:
            p = 1.0 / np.arange(1, len(asked) + 1) ** s
            q = asked[int(rng.choice(len(asked), p=p / p.sum()))]
        questions.append(q)
    return Schedule(list(cat[:warm_n]), due, questions, new)


@dataclass
class Record:
    """One query of the window, on the client's clock (perf_counter)."""

    index: int
    due: float
    sent: float = float("nan")
    done: float = float("nan")
    answer: object = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.answer is not None


@dataclass
class OpenLoop:
    """Sends ``questions`` at their due times over ``clients`` connections
    and records each answer.  ``make_client(address)`` opens a connection
    with a ``query(Query)`` method; ``make_query(payload)`` builds the
    query; ``span`` wraps each call in a host span (or does nothing)."""

    make_client: object
    make_query: object
    clients: int
    span: object = None
    records: List[Record] = field(default_factory=list)

    def open(self, address, sched: Schedule) -> None:
        """Connect and build the queries (before the window opens)."""
        self.conns = [self.make_client(address) for _ in range(self.clients)]
        self.queries = [self.make_query(q) for q in sched.questions]

    def run(self, sched: Schedule, t0: float,
            grace_s: float) -> List[Record]:
        """Blocks until every query has its outcome or ``grace_s`` past
        the last due time has passed; a query with no outcome by then is
        recorded as failed."""
        self.records = [Record(i, t0 + float(d))
                        for i, d in enumerate(sched.due)]
        work: "queue.Queue" = queue.Queue()
        conns, queries = self.conns, self.queries

        def worker(conn):
            while True:
                rec = work.get()
                if rec is None:
                    return
                rec.sent = time.perf_counter()
                try:
                    if self.span is None:
                        ans = conn.query(queries[rec.index])
                    else:
                        with self.span("query"):
                            ans = conn.query(queries[rec.index])
                    rec.done = time.perf_counter()
                    rec.answer = ans
                except Exception as e:     # noqa: BLE001 — a failed query
                    rec.done = time.perf_counter()
                    rec.error = f"{type(e).__name__}: {e}"

        threads = [threading.Thread(target=worker, args=(c,), daemon=True)
                   for c in conns]
        for th in threads:
            th.start()
        try:
            for rec in self.records:
                wait = rec.due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                work.put(rec)
            for _ in threads:
                work.put(None)
            end = (t0 + (float(sched.due[-1]) if len(sched.due) else 0.0)
                   + grace_s)
            for th in threads:
                th.join(timeout=max(0.0, end - time.perf_counter()))
        finally:
            for c in conns:
                c.close()
        for th in threads:
            th.join(timeout=10.0)
        for rec in self.records:
            if not rec.ok and rec.error is None:
                rec.error = "no answer within the grace period"
                rec.done = end
        return self.records
