"""The comparison that decides ``correct``: what the timed path produced,
against the plain reference (``reference.py``) and the copied ranking
(``ranking.py``), each number beside its own limit.

Relative errors are |program - reference| / |reference|.  The reference
evaluates the model exactly (float64 over integer latencies and float32
knob values is exact at these magnitudes), so arrivals that tie in exact
arithmetic tie there and queue in access order.  The program computes in
float32, where such ties can fall either way; a tie that falls the other
way moves one cell of one candidate by up to a few tenths of a percent.
So each kind of value is held by two numbers: its largest error, whose
limit leaves room for a tie, and the share of values off by more than
``OFF``, whose limit leaves room for the few candidates with ties.
"""

from __future__ import annotations

import json
from typing import Dict, List, Sequence

import numpy as np

from ranking import objectives, pareto_front

OFF = 1e-5          # a value off by more than this counts as "off"


def rel_err(a, b) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-30)


def resolve(cells, workload, archs) -> List[int]:
    """Matrix columns a question covers (reference cells)."""
    return [i for i, c in enumerate(cells)
            if (workload is None or c.workload == workload)
            and (archs is None or c.arch in set(archs))]


def pin(pool: np.ndarray, knob_names: Sequence[str], overrides: Dict
        ) -> np.ndarray:
    """A question's candidate block: the pool with pinned knob columns."""
    cand = np.array(pool, np.float32, copy=True)
    for name, val in overrides.items():
        cand[:, list(knob_names).index(name)] = val
    return cand


def sweep_numbers(calls: List[Dict], ref_rows: Dict[int, Dict],
                  base_c: np.ndarray, base_e: np.ndarray) -> Dict[str, float]:
    """``calls``: per explore call in the window, its block index and what
    it returned (``cycles``, ``latency``, ``energy``, ``cost``,
    ``pareto``).  ``ref_rows[block]``: the reference on a seeded sample of
    that block's rows (``rows``, ``cycles``, ``energy``, ``cost``)."""
    c_err, o_err, mism = [], [], 0
    for call in calls:
        objs = np.stack([call["latency"], call["energy"], call["cost"]], 1)
        if not np.array_equal(pareto_front(objs), call["pareto"]):
            mism += 1
        r = ref_rows[call["block"]]
        rows = r["rows"]
        c_err.append(rel_err(call["cycles"][rows], r["cycles"]).ravel())
        ref_obj = objectives(r["cycles"], r["energy"], r["cost"], base_c,
                             base_e, range(base_c.shape[0]))
        o_err.append(rel_err(objs[rows], ref_obj).ravel())
    c = np.concatenate(c_err) if c_err else np.zeros(1)
    o = np.concatenate(o_err) if o_err else np.zeros(1)
    return {"cycles_err_max": float(c.max()),
            "cycles_off_share": float((c > OFF).mean()),
            "objective_err_max": float(o.max()),
            "front_mismatch": float(mism)}


def serve_numbers(answers: List, questions: List[Dict], ref_block,
                  cells, lost: int) -> Dict[str, float]:
    """``answers[i]`` is the served answer to ``questions[i]`` (payload).
    Each distinct question counts once: its first answer is compared with
    the reference, and every repeat has to say the same as the first.
    ``ref_block(overrides)`` gives the reference over that block: a dict
    with ``cand``, ``cycles``, ``energy``, ``cost``, ``base_c``,
    ``base_e``."""
    errs, mism = [], 0
    by_q: Dict[str, List] = {}
    for ans, q in zip(answers, questions):
        by_q.setdefault(json.dumps(q, sort_keys=True), [q]).append(ans)
    for q, ans, *repeats in by_q.values():
        cols = resolve(cells, q["workload"], q["archs"])
        r = ref_block(q["overrides"])
        cand = r["cand"]
        ref_obj = objectives(r["cycles"], r["energy"], r["cost"],
                             r["base_c"], r["base_e"], cols)
        got = []
        for d in ans.designs:
            hit = np.flatnonzero((cand == np.asarray(d.theta, np.float32))
                                 .all(axis=1))
            if hit.size == 0:
                errs.append(np.array([np.inf]))
                got.append(-1)
                continue
            i = int(hit[0])
            got.append(i)
            errs.append(rel_err(d.cycles, r["cycles"][i, cols]))
            errs.append(rel_err([d.latency, d.energy, d.cost], ref_obj[i]))
        # "which accelerator": the served arch's cell runs the lead design
        # at the lowest baseline-relative latency, up to a near-tie
        rel = r["cycles"][got[0] if got else 0, cols] / r["base_c"][cols]
        arch_ok = any(cells[c].arch == ans.best_arch
                      and rel[j] <= rel.min() * (1 + OFF)
                      for j, c in enumerate(cols))
        same = all(_same(ans, a) for a in repeats)
        if not ranking_ok(ref_obj, got, q["top_k"]) or not arch_ok or \
                [cells[i].name for i in cols] != list(ans.cells) or not same:
            mism += 1
    e = np.concatenate(errs) if errs else np.zeros(1)
    return {"design_err_max": float(e.max()),
            "design_off_share": float((e > OFF).mean()),
            "rank_mismatch_share": mism / max(1, len(by_q)),
            "lost": float(lost)}


def ranking_ok(obj: np.ndarray, served: Sequence[int], k: int,
               eps: float = OFF) -> bool:
    """Whether ``served`` (row ids, in order) is the top-``k`` of the
    Pareto front of ``obj`` (reference objectives, lower is better) up to
    near-ties: two rows whose objectives lie within ``eps`` (relative) of
    each other may rank either way, because the program ranks its own
    float32 objectives.  It fails when a served row is dominated by a
    margin in every objective, when a row that is on the front by a margin
    and clearly faster than the last served row is missing, or when the
    served rows are out of latency order by more than a near-tie."""
    served = list(served)
    if any(i < 0 for i in served) or len(served) > k:
        return False
    scale = np.abs(obj)
    # near[j, i]: row j is at least as good as row i, up to a near-tie
    near = (obj[:, None, :] <= obj[None, :, :] + eps * scale[None]).all(2)
    np.fill_diagonal(near, False)
    # clear[j, i]: row j is better than row i by a margin everywhere
    clear = (obj[:, None, :] < obj[None, :, :] - eps * scale[None]).all(2)
    if any(clear[:, i].any() for i in served):
        return False
    lat = obj[:, 0]
    for a, b in zip(served, served[1:]):
        if lat[a] > lat[b] + eps * abs(lat[b]):
            return False
    sure = np.flatnonzero(~near.any(axis=0))
    if len(served) < k:
        return set(sure) <= set(served)
    last = lat[served[-1]] - eps * abs(lat[served[-1]])
    return all(r in served for r in sure if lat[r] < last)


def _same(a, b) -> bool:
    """Two answers to one question say the same thing."""
    key = lambda x: (x.best_arch, tuple(x.cells), tuple(
        (d.theta, d.latency, d.energy, d.cost, tuple(d.cycles))
        for d in x.designs))
    return key(a) == key(b)


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit; a number without a limit, or
    a limit without a number, fails."""
    if set(numbers) != set(limits):
        return False
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in numbers)
