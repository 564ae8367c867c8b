"""Plain reference for `select id ... order by l2_distance(v, q) limit k`
over the seeded vectors, in numpy.  It imports nothing of the program and
takes nothing the program made: not its centroids, its lists or its ids.

An IVF index is approximate, so no independent reference reproduces its
answer id for id.  What every answer must satisfy, whatever the lists:

  vec_answers_malformed   answers that are not k distinct ids of stored
                          rows in ascending order of their exact float64
                          distance (limit 0; a descent under 1e-9 of the
                          distance is float64 rounding, not an order)
  vec_recall_deficit      1 - mean overlap with the exact top-k (brute
                          force) over every answer of the window

Staged, not proven enough (PR 26 review; PERF.md section 7): the cells
that name this reference are out of BENCHMARK.json.  The configuration
states float32, and this comparison cannot see a step below it: the
statement re-ranks the k fetched rows by an exact distance, so the search's
arithmetic shows only in which ids it picks, and brute force over
int8-rounded vectors loses 0.02-0.05 of the neighbours where the index
loses 0.11 (`int8_round`, kept for that reading), inside the limit of
`vec_recall_deficit`.  The control here breaks a guarantee instead, that
every acknowledged row is read back: `control_answers` is the brute-force
reference over the first half of the commits only.  The limit was read on
an engine that was not re-opened.  The PR that brings a vector cell brings
a reference of its own beside this one, with a control one precision step
down and a number that separates it, and reads its limits anew.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Limits, each set from two readings (my chip runs, PR 26, on an engine
# that was not re-opened: recall deficit 0.0896-0.1424 over 24 seeds, the
# half-data control 0.4688-0.4799 on 3 seeds).
LIMITS = {"vec_answers_malformed": 0, "vec_statements_failed": 0,
          "vec_recall_deficit": 0.32}
ORDER_TOLERANCE = 1e-9
BLOCK = 1 << 17


def _top(d, ids, keep):
    """The `keep` smallest of each row of d, with their ids."""
    if d.shape[1] > keep:
        part = np.argpartition(d, keep, axis=1)[:, :keep]
        return np.take_along_axis(d, part, 1), np.take_along_axis(ids, part, 1)
    return d, ids


def brute_force_topk(x, q, k):
    """Exact top-k ids per query: float32 matmuls over blocks of rows keep
    4k candidates, which are then ranked by float64 distances."""
    if len(q) == 0:
        return np.empty((0, k), np.int64)
    keep = 4 * k
    chunks = np.array_split(np.arange(len(q)), min(len(q), os.cpu_count() or 4))
    best_d = [np.empty((len(c), 0), np.float32) for c in chunks]
    best_i = [np.empty((len(c), 0), np.int64) for c in chunks]
    with ThreadPoolExecutor(len(chunks)) as pool:
        for lo in range(0, len(x), BLOCK):
            xb = x[lo:lo + BLOCK]
            d = np.einsum("nd,nd->n", xb, xb)[None, :] - 2.0 * (q @ xb.T)
            ids = np.arange(lo, lo + len(xb), dtype=np.int64)

            def merge(j, d=d, ids=ids):
                c = chunks[j]
                bd, bi = _top(d[c], np.broadcast_to(ids, (len(c), len(ids))),
                              keep)
                best_d[j], best_i[j] = _top(
                    np.concatenate([best_d[j], bd], 1),
                    np.concatenate([best_i[j], bi], 1), keep)

            list(pool.map(merge, range(len(chunks))))
    cand = np.concatenate(best_i)
    out = np.empty((len(q), k), np.int64)
    for lo in range(0, len(q), 128):
        c = cand[lo:lo + 128]
        d = ((x[c].astype(np.float64)
              - q[lo:lo + 128, None, :].astype(np.float64)) ** 2).sum(-1)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        out[lo:lo + 128] = np.take_along_axis(c, order, 1)
    return out


def int8_round(v):
    """Symmetric per-vector int8: the values an int8 kernel would see."""
    scale = np.abs(v).max(axis=1, keepdims=True) / 127.0
    scale[scale == 0] = 1.0
    return (np.rint(v / scale) * scale).astype(np.float32)


def _answers(cfg, data, executed):
    """-> (query index, ids [k]) of the well-formed answers, and how many
    were malformed or failed."""
    x, q, k = data["x"], data["queries"], cfg["k"]
    js, ids, malformed, failed = [], [], 0, 0
    for st in executed:
        if st["error"] is not None:
            failed += 1
            continue
        j = st["params"]["query"]
        try:
            got = [int(r[0]) for r in st["rows"]]
        except (TypeError, ValueError, IndexError):
            got = []
        if (len(got) != k or len(set(got)) != k
                or min(got) < 0 or max(got) >= len(x)):
            malformed += 1
            continue
        d = ((x[got].astype(np.float64) - q[j].astype(np.float64)) ** 2).sum(1)
        if ((d[:-1] - d[1:]) > ORDER_TOLERANCE * d[1:]).any():
            malformed += 1
            continue
        js.append(j)
        ids.append(got)
    return (np.asarray(js, np.int64),
            np.asarray(ids, np.int64).reshape(-1, k), malformed, failed)


def compare(cfg, data, executed):
    """Every answer of the window.  -> (numbers {name: [value, limit]},
    facts {"recall_at_k": mean overlap with the exact top-k})."""
    x, q, k = data["x"], data["queries"], cfg["k"]
    js, ids, malformed, failed = _answers(cfg, data, executed)
    used = np.unique(js)
    truth = dict(zip(used.tolist(), brute_force_topk(x, q[used], k)))
    recall = float(np.mean([len(set(a.tolist()) & set(truth[j].tolist())) / k
                            for j, a in zip(js.tolist(), ids)])
                   ) if len(js) else 0.0
    numbers = {
        "vec_answers_malformed": [malformed, LIMITS["vec_answers_malformed"]],
        "vec_statements_failed": [failed, LIMITS["vec_statements_failed"]],
        "vec_recall_deficit": [1.0 - recall, LIMITS["vec_recall_deficit"]]}
    return numbers, {"recall_at_k": recall}


def control_answers(cfg, data, executed, sample=256):
    """The first `sample` distinct queries of the window answered by brute
    force over the first half of the commits only (rows 0 .. n/2).
    -> executed, with the control's rows."""
    x, q, k = data["x"], data["queries"], cfg["k"]
    seen, picked = set(), []
    for st in executed:
        j = st["params"]["query"]
        if j not in seen and len(seen) < sample:
            seen.add(j)
            picked.append(st)
    js = np.asarray([st["params"]["query"] for st in picked])
    ids = brute_force_topk(x[:len(x) // 2], q[js], k)
    return [dict(st, rows=[[str(i)] for i in row], error=None)
            for st, row in zip(picked, ids.tolist())]
