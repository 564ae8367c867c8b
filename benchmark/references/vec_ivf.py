"""Plain reference for `select id ... order by l2_distance(v, q) limit k`
over the seeded vectors of `vec-wiki-1m-ivf`, in numpy with float64
distances.  It imports nothing of the program and takes nothing the
program made: not its centroids, its lists, its distances or its ids.

An IVF index is approximate, so no independent reference reproduces its
answer id for id.  What every answer of the window must satisfy, whatever
the lists:

  ivf_statements_failed   statements that ended in an error (limit 0)
  ivf_answers_malformed   answers that are not k distinct ids of stored
                          rows (limit 0)
  ivf_order_descent       the largest step DOWN between two neighbours of
                          an answer, as a share of the distance: over all
                          answers, max (d[i] - d[i+1]) / d[i+1] with d the
                          exact float64 squared distance of the ids in the
                          order they were answered; 0 where every answer
                          ascends.  The configuration states float32: a
                          float32 sum of 768 squares rounds each partial
                          sum to 2^-24 of itself, so two rows closer than
                          about sqrt(768) * 2^-24 = 1.7e-6 of their
                          distance (at worst 768 * 2^-24 = 4.6e-5) may
                          come out either way round; anything coarser is an
                          order, not a rounding.  The limit lies between
                          what the program reads and what the bfloat16
                          control reads (PERF.md section 2).
  ivf_recall_deficit      1 - mean overlap of the answers with the exact
                          top-k (brute force over every row)

The control is one precision step under float32: the program's own answers
(the same ids) re-ranked by distances computed from the stored vectors and
the query rounded to bfloat16, which is what a kernel that reads bfloat16
would return.  It keeps every id, so the recall is the program's; it has to
trip `ivf_order_descent`, alone.  The recall number's upper reading comes
from a planted fault instead (`tests/test_vec_ivf.py`, and PERF.md section
2 for the reading at the timed size): the rows of one of the four commits
out of a search's reach, which trips `ivf_recall_deficit`, alone.

What this comparison cannot see: the index search's own arithmetic (its
bfloat16 scoring of the probed lists, below the float32 the answer's order
is held to) is masked by the 3x overfetch and the exact re-rank, and shows
only in which ids are found, that is in the recall, where it is small
beside what probing 8 of 1,024 lists loses.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# Limits, each set from two readings (PERF.md section 2; my chip runs,
# PR 29, on the re-opened engine, 28 runs of the two cells, the control
# on 7 of them): `ivf_order_descent` reads at most 1.29e-7 for the program
# (0 in 21 runs) and at least 5.43e-4 for the bfloat16 control;
# `ivf_recall_deficit` reads 0.0837-0.1233, and its limit is the largest
# reading plus five times the interquartile distance of the readings
# (0.0153); the control keeps every id, so the upper reading is a planted
# fault's: one commit's rows out of a search's reach (PERF.md section 2).
LIMITS = {"ivf_statements_failed": 0, "ivf_answers_malformed": 0,
          "ivf_order_descent": 1e-5, "ivf_recall_deficit": 0.20}
BLOCK = 1 << 16


def bf16_round(v):
    """float32 -> the nearest bfloat16 (ties to even), as float32."""
    bits = np.ascontiguousarray(v, np.float32).view(np.uint32)
    bits = bits + (np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                        & np.uint32(1)))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


def _dist64(x, ids, qv):
    """Exact float64 squared distances of rows `ids` to one query."""
    diff = x[ids].astype(np.float64) - qv.astype(np.float64)
    return np.einsum("nd,nd->n", diff, diff)


def brute_force_topk(x, q, k):
    """Exact top-k ids per query: float32 matmuls over blocks of rows keep
    4k candidates a query, which are then ranked by float64 distances."""
    if len(q) == 0:
        return np.empty((0, k), np.int64)
    keep = min(4 * k, len(x))
    workers = os.cpu_count() or 4

    def block(lo):
        xb = x[lo:lo + BLOCK]
        d = np.einsum("nd,nd->n", xb, xb)[None, :] - 2.0 * (q @ xb.T)
        if d.shape[1] > keep:
            part = np.argpartition(d, keep - 1, axis=1)[:, :keep]
            return np.take_along_axis(d, part, 1), part + lo
        return d, np.broadcast_to(np.arange(lo, lo + len(xb)), d.shape)

    with ThreadPoolExecutor(workers) as pool:
        parts = list(pool.map(block, range(0, len(x), BLOCK)))
    d = np.concatenate([p[0] for p in parts], 1)
    cand = np.concatenate([p[1] for p in parts], 1).astype(np.int64)
    if d.shape[1] > keep:
        part = np.argpartition(d, keep - 1, axis=1)[:, :keep]
        cand = np.take_along_axis(cand, part, 1)
    out = np.empty((len(q), k), np.int64)
    for j in range(len(q)):
        order = np.argsort(_dist64(x, cand[j], q[j]), kind="stable")[:k]
        out[j] = cand[j][order]
    return out


def _answers(cfg, data, executed):
    """-> (query index [n], ids [n, k]) of the well-formed answers, and
    how many were malformed or failed."""
    n_rows, k = len(data["x"]), cfg["k"]
    js, ids, malformed, failed = [], [], 0, 0
    for st in executed:
        if st["error"] is not None:
            failed += 1
            continue
        try:
            got = [int(r[0]) for r in st["rows"]]
        except (TypeError, ValueError, IndexError):
            got = []
        if (len(got) != k or len(set(got)) != k
                or min(got) < 0 or max(got) >= n_rows):
            malformed += 1
            continue
        js.append(st["params"]["query"])
        ids.append(got)
    return (np.asarray(js, np.int64),
            np.asarray(ids, np.int64).reshape(-1, k), malformed, failed)


def compare(cfg, data, executed):
    """Every answer of the window.  -> (numbers {name: [value, limit]},
    facts {"recall_at_k": mean overlap with the exact top-k})."""
    x, q, k = data["x"], data["queries"], cfg["k"]
    js, ids, malformed, failed = _answers(cfg, data, executed)
    descent = 0.0
    for j, got in zip(js.tolist(), ids):
        d = _dist64(x, got, q[j])
        descent = max(descent, float(((d[:-1] - d[1:]) / d[1:]).max()))
    used = np.unique(js)
    truth = dict(zip(used.tolist(), brute_force_topk(x, q[used], k)))
    recall = float(np.mean([len(set(a.tolist()) & set(truth[j].tolist())) / k
                            for j, a in zip(js.tolist(), ids)])
                   ) if len(js) else 0.0
    numbers = {
        "ivf_statements_failed": [failed, LIMITS["ivf_statements_failed"]],
        "ivf_answers_malformed": [malformed, LIMITS["ivf_answers_malformed"]],
        "ivf_order_descent": [descent, LIMITS["ivf_order_descent"]],
        "ivf_recall_deficit": [1.0 - recall, LIMITS["ivf_recall_deficit"]]}
    return numbers, {"recall_at_k": recall}


def control_answers(cfg, data, executed):
    """The window's own answers, each re-ranked by float32 distances of
    the bfloat16-rounded rows to the bfloat16-rounded query.
    -> executed, with the control's rows (failed and malformed answers
    are passed through as they were)."""
    x, q, k = data["x"], data["queries"], cfg["k"]
    qb = bf16_round(q)
    out = []
    for st in executed:
        try:
            got = np.asarray([int(r[0]) for r in st["rows"]], np.int64)
            assert st["error"] is None and len(got) == k
            assert got.min() >= 0 and got.max() < len(x)
        except (TypeError, ValueError, IndexError, AssertionError):
            out.append(st)
            continue
        diff = bf16_round(x[got]) - qb[st["params"]["query"]]
        d = np.einsum("nd,nd->n", diff, diff)
        order = np.argsort(d, kind="stable")
        out.append(dict(st, rows=[[str(i)] for i in got[order].tolist()]))
    return out
