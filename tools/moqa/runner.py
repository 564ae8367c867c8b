"""moqa config-lattice lockstep runner.

One invariant, many configurations: every execution configuration of
this engine must return the SAME answer.  The runner executes each
generated query under a BASELINE configuration (per-operator path,
serving caches off) and then under paired variant configurations, and
diffs the row-sets exactly:

  fusion          MO_PLAN_FUSION=1 + MO_FUSION_MIN_ROWS=0 (traced
                  whole-plan programs) vs the per-operator path
  dense-groups    MO_DENSE_GROUPS=0 (general hash group path) vs the
                  mixed-radix dense path (floats tolerant: reduction
                  order is config-dependent here by design)
  plan-cache      warm plan-cache hit vs cold compile
  result-cache    warm result-cache hit vs recompute
  udf-tier        MO_UDF_JIT=0 row loop vs jit tier
  canary          padding canary armed (utils/qa.py poisons padded
                  tails) vs disarmed — plus the canary audits; the
                  armed run also forces MO_NARROW_ENCODINGS=1 so the
                  poisoned tails sweep the narrow dict-code path too
  narrow-encodings  MO_NARROW_ENCODINGS=1 fused path (int8/int16 dict
                  codes, bf16 float lanes) vs the wide baseline, swept
                  over GROUPED queries (the only shape where the
                  policy engages); the corpus carries no FLOAT32
                  column (doubles stay f64, decimals/counts stay
                  scaled int64) so this pair is EXACT — the bf16
                  tolerance contract is proven by the dedicated f32
                  drill (_run_narrow_f32_drill)
  mview           insert-then-query ≡ query-over-materialized-view,
                  incremental maintenance AND full refresh
  shards          SET ivf_shards=2 cluster-sharded vector search vs
                  local (virtual device mesh permitting)
  cache-stale     warm fusion/plan/result caches, mutate the table,
                  re-run: cached artifacts must never outlive the data

Oracles (tools/moqa/oracles.py) run against the baseline session.
Findings are reduced (tools/moqa/reducer.py) to minimal repros.
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from tools.moqa.generator import GenQuery, Generator, Scenario
from tools.moqa import oracles as ORC

# ---------------------------------------------------------------- env

#: the baseline lattice point: per-operator execution, default group
#: path, jit UDF tier, no fusion
ENV_BASELINE = {"MO_PLAN_FUSION": "0", "MO_DENSE_GROUPS": None,
                "MO_FUSION_MIN_ROWS": None, "MO_UDF_JIT": None,
                "MO_NARROW_ENCODINGS": None}

#: per-pair env overrides (applied on top of the baseline)
PAIR_ENV = {
    "fusion": {"MO_PLAN_FUSION": "1", "MO_FUSION_MIN_ROWS": "0"},
    "dense-groups": {"MO_DENSE_GROUPS": "0"},
    "plan-cache": {},
    "result-cache": {},
    "udf-tier": {"MO_UDF_JIT": "0"},
    # the armed replay also runs the narrow dict codes
    "canary": {"MO_PLAN_FUSION": "1", "MO_FUSION_MIN_ROWS": "0",
               "MO_NARROW_ENCODINGS": "1"},
    "narrow-encodings": {"MO_NARROW_ENCODINGS": "1",
                         "MO_PLAN_FUSION": "1",
                         "MO_FUSION_MIN_ROWS": "0"},
    "mview": {},
    "shards": {},
    # device-shard SQL executor (parallel/dist_query.py): the variant
    # SETs query_shards live, same mechanism as the ivf "shards" pair
    "query-shards": {},
    "cache-stale": {"MO_PLAN_FUSION": "1", "MO_FUSION_MIN_ROWS": "0"},
}

#: pairs whose two sides are bit-identical by construction; the rest
#: compare floats at 9 significant digits (reduction order differs:
#: the general hash group path and incremental mview delta maintenance
#: both sum floats in a different order than the baseline recompute —
#: decimal/int sums stay exact everywhere)
EXACT_PAIRS = frozenset({"fusion", "plan-cache", "result-cache",
                         "udf-tier", "canary", "shards",
                         "cache-stale", "narrow-encodings"})

PAIR_NAMES = tuple(PAIR_ENV)


@contextmanager
def env_scope(overrides: Dict[str, Optional[str]]):
    saved = {k: os.environ.get(k) for k in overrides}
    try:
        for k, v in overrides.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _pair_scope(pair: str):
    env = dict(ENV_BASELINE)
    env.update(PAIR_ENV[pair])
    return env_scope(env)


# ------------------------------------------------------------ findings

class Finding:
    """One corpus finding: a configuration or oracle disagreement.
    `query` keeps the structured GenQuery (when the finding came from
    one) so the reducer can shrink clauses instead of parsing SQL;
    `partition` keeps the TLP/NoREC partition predicate."""

    __slots__ = ("kind", "scenario", "seed", "pair", "sql", "detail",
                 "repro", "query", "partition")

    def __init__(self, kind, scenario, seed, pair, sql, detail,
                 repro=None, query=None, partition=None):
        self.kind = kind
        self.scenario = scenario
        self.seed = seed
        self.pair = pair
        self.sql = sql
        self.detail = detail
        self.repro = repro
        self.query = query
        self.partition = partition

    def format(self) -> str:
        return (f"[{self.kind}] seed={self.seed} scenario="
                f"{self.scenario} pair={self.pair}\n  query: {self.sql}"
                f"\n  {self.detail}")

    def as_dict(self) -> dict:
        return {"kind": self.kind, "scenario": self.scenario,
                "seed": self.seed, "pair": self.pair, "sql": self.sql,
                "detail": self.detail, "repro": self.repro}


# ------------------------------------------------------- live scenario

class LiveScenario:
    """A scenario instantiated on a fresh in-memory engine."""

    def __init__(self, scenario: Scenario, waves: int = 2,
                 serving_off: bool = True):
        from matrixone_tpu.frontend import Session
        from matrixone_tpu.storage.engine import Engine
        self.scenario = scenario
        self.eng = Engine()
        self.sess = Session(catalog=self.eng)
        self.sess.execute(scenario.create_sql())
        rows = (scenario.rows if waves >= 2
                else scenario.rows[:scenario.wave_split])
        if rows:
            self.sess.execute(scenario.insert_sql(rows))
        for ddl in scenario.setup_sql:
            self.sess.execute(ddl)
        if serving_off:
            self.ctl("serving", "plan:off")
    # (result cache is off by default: MO_RESULT_CACHE_MB=0)

    def ctl(self, cmd: str, arg: str) -> str:
        r = self.sess.execute(f"select mo_ctl('{cmd}', '{arg}')")
        return r.rows()[0][0]

    def insert_wave2(self):
        sc = self.scenario
        rest = sc.rows[sc.wave_split:]
        if rest:
            self.sess.execute(sc.insert_sql(rest))

    def rows(self, sql: str) -> List[tuple]:
        return self.sess.execute(sql).rows()

    def close(self):
        self.sess.close()


def _ordered(q: GenQuery) -> bool:
    return q.has("ordered")


def _applicable(pair: str, q: GenQuery) -> bool:
    if pair in ("fusion", "plan-cache", "result-cache", "canary",
                "cache-stale"):
        return not q.has("vector")
    if pair == "narrow-encodings":
        # the policy only bites on fused agg lanes / dict codes — a
        # grouped-only sweep covers every engaged code path at a
        # fraction of the lockstep cost (the f32 drill below carries
        # the precision teeth)
        return q.has("grouped")
    if pair == "dense-groups":
        return q.has("grouped")
    if pair == "udf-tier":
        return q.has("udf")
    if pair == "mview":
        return q.has("maintainable")
    if pair == "shards":
        return q.has("vector")
    if pair == "query-shards":
        # every non-vector family: the executor itself degrades to the
        # local plan when the shape doesn't shard, and THAT ladder is
        # exactly what the lockstep pair must exercise
        return not q.has("vector")
    return False


def _mesh_ok(n: int = 2) -> bool:
    import jax
    try:
        return len(jax.devices()) >= n
    except RuntimeError:
        return False


# =====================================================================
# the corpus run
# =====================================================================

def run_corpus(seed: int = 0, queries_per_scenario: int = 80,
               pairs: Optional[List[str]] = None,
               time_budget_s: Optional[float] = None,
               reduce_findings: int = 4,
               oracle_fraction: float = 0.34,
               stale_fraction: float = 0.2,
               max_views: int = 10) -> dict:
    """Run the full differential corpus for one seed.  Returns a report
    dict (see `format_report`); report['findings'] empty == the
    invariant held everywhere the corpus looked."""
    from matrixone_tpu.utils import qa

    t0 = time.monotonic()
    gen = Generator(seed)
    scenarios = gen.scenarios()
    pairs = list(PAIR_NAMES) if pairs is None else list(pairs)
    if "shards" in pairs and not _mesh_ok():
        pairs.remove("shards")
    if "query-shards" in pairs and not _mesh_ok():
        pairs.remove("query-shards")
    findings: List[Finding] = []
    checks: Dict[str, int] = {}
    pair_counts: Dict[str, int] = {p: 0 for p in pairs}
    n_queries = 0
    deadline = (t0 + time_budget_s) if time_budget_s else None

    def note(oracle: str):
        checks[oracle] = checks.get(oracle, 0) + 1
        qa.note_check(oracle)

    def found(kind, scenario, pair, sql, detail, q=None,
              partition=None):
        findings.append(Finding(kind, scenario, seed, pair, sql,
                                detail, query=q, partition=partition))
        if not kind.startswith("canary-"):
            # canary events already drove mo_qa_findings_total at the
            # audit point (qa.record_finding) — don't double-count
            qa.note_finding(kind)

    for sc in scenarios:
        if deadline and time.monotonic() > deadline:
            break
        n_q = queries_per_scenario if "vector" not in sc.features \
            else max(8, queries_per_scenario // 5)
        if "join_scenario" in sc.features:
            # the join/window scenario rides every non-vector pair too;
            # half the per-scenario budget keeps the tier-1 gate bounded
            n_q = max(12, n_q // 2)
        qs = gen.queries(sc, n_q)
        n_queries += len(qs)
        qa.note_query(len(qs))

        live = LiveScenario(sc)
        base_rows: Dict[int, List[tuple]] = {}
        base_err: Dict[int, str] = {}
        try:
            with env_scope(ENV_BASELINE):
                for i, q in enumerate(qs):
                    try:
                        base_rows[i] = live.rows(q.sql())
                    except Exception as e:  # noqa: BLE001 — a baseline
                        # rejection is itself a corpus finding (dialect
                        # drift between generator and engine)
                        base_err[i] = repr(e)
                        found("gen-error", sc.name, "baseline",
                              q.sql(), repr(e))
                # ---- metamorphic oracles on the baseline session
                _run_oracles(live, sc, qs, base_rows, base_err, gen,
                             oracle_fraction, note, found)

            # ---- same-session env pairs
            for pair in ("fusion", "dense-groups", "udf-tier",
                         "narrow-encodings", "shards", "query-shards"):
                if pair not in pairs:
                    continue
                if pair == "shards":
                    # sharding is a SESSION variable, not env: the
                    # session snapshots MO_IVF_SHARDS at creation, so
                    # the variant must SET it live (and restore)
                    live.sess.execute("set ivf_shards = 2")
                if pair == "query-shards":
                    # same mechanism for the SQL device-shard executor;
                    # dist_min_rows drops so the tiny corpus tables
                    # actually shard (restored below)
                    live.sess.execute("set query_shards = 2")
                    live.sess.execute("set dist_min_rows = 0")
                try:
                    with _pair_scope(pair):
                        taken = 0
                        for i, q in enumerate(qs):
                            if i in base_err \
                                    or not _applicable(pair, q):
                                continue
                            if pair == "narrow-encodings":
                                # half-stride sample: the pair is a
                                # config sweep over one policy flip —
                                # every other grouped query keeps every
                                # engaged shape in the gate's budget
                                # (the f32 drill carries the teeth)
                                taken += 1
                                if taken % 2 == 0:
                                    continue
                            _diff_one(live, q, base_rows[i], pair, sc,
                                      note, found, pair_counts)
                finally:
                    if pair == "shards":
                        live.sess.execute("set ivf_shards = 0")
                    if pair == "query-shards":
                        live.sess.execute("set query_shards = 0")
                        live.sess.execute("set dist_min_rows = 100000")

            # ---- warm-cache pairs (same session, caches on)
            if "plan-cache" in pairs:
                live.ctl("serving", "plan:on")
                with _pair_scope("plan-cache"):
                    for i, q in enumerate(qs):
                        if i in base_err or not _applicable(
                                "plan-cache", q):
                            continue
                        _diff_one(live, q, base_rows[i], "plan-cache",
                                  sc, note, found, pair_counts,
                                  runs=2)
                live.ctl("serving", "plan:off")
            if "result-cache" in pairs:
                live.ctl("serving", "result:on")
                with _pair_scope("result-cache"):
                    for i, q in enumerate(qs):
                        if i in base_err or not _applicable(
                                "result-cache", q):
                            continue
                        _diff_one(live, q, base_rows[i],
                                  "result-cache", sc, note, found,
                                  pair_counts, runs=2)
                live.ctl("serving", "result:off")
                live.ctl("serving", "clear")
        finally:
            live.close()

        # ---- pairs needing their own engine
        if "canary" in pairs and "vector" not in sc.features:
            _run_canary_pair(sc, qs, base_rows, base_err, note, found,
                             pair_counts)
        if "mview" in pairs and "vector" not in sc.features:
            _run_mview_pair(sc, qs, base_rows, base_err, note, found,
                            pair_counts, max_views)
        if "cache-stale" in pairs and "vector" not in sc.features:
            _run_stale_pair(sc, qs, base_err, note, found, pair_counts,
                            stale_fraction)

    # ---- narrow-encodings f32 drill (own tables: the corpus carries
    # no FLOAT32 column, so the bf16 compute-lane tolerance needs its
    # own deliberately bf16-inexact data)
    if "narrow-encodings" in pairs:
        _run_narrow_f32_drill(seed, note, found, pair_counts)

    # ---- reduce the first few findings to minimal repros
    reduced = 0
    if reduce_findings:
        from tools.moqa import reducer
        for f in findings:
            if reduced >= reduce_findings:
                break
            if f.kind in ("gen-error",):
                continue
            try:
                f.repro = reducer.reduce_finding(f, gen)
                reduced += 1
            except Exception as e:  # noqa: BLE001 — reduction is best-
                # effort; the un-reduced finding still fails the gate
                f.repro = f"<reduction failed: {e!r}>"

    report = {
        "seed": seed,
        "queries": n_queries,
        "scenarios": [sc.name for sc in scenarios],
        "pairs": {p: pair_counts.get(p, 0) for p in pairs},
        "oracle_checks": checks,
        "total_checks": sum(checks.values()),
        "findings": [f.as_dict() for f in findings],
        "findings_formatted": [f.format() for f in findings],
        "seconds": round(time.monotonic() - t0, 2),
    }
    _remember(report)
    return report


def _diff_one(live: LiveScenario, q: GenQuery, base: List[tuple],
              pair: str, sc: Scenario, note, found, pair_counts,
              runs: int = 1):
    tol = pair not in EXACT_PAIRS
    try:
        got = None
        for _ in range(runs):
            got = live.rows(q.sql())
    except Exception as e:  # noqa: BLE001 — an error on one side of a
        # lockstep pair IS the finding
        found("error-divergence", sc.name, pair, q.sql(),
              f"variant raised {e!r} but baseline succeeded", q=q)
        return
    note("lockstep")
    pair_counts[pair] = pair_counts.get(pair, 0) + 1
    d = ORC.diff_rows(base, got, ordered=_ordered(q),
                      tol_floats=tol)
    if d is not None:
        found("lockstep-mismatch", sc.name, pair, q.sql(), d, q=q)


def _run_oracles(live, sc, qs, base_rows, base_err, gen,
                 fraction, note, found):
    if fraction <= 0 or "vector" in sc.features:
        return
    conn = ORC.sqlite_setup(sc)
    try:
        for i, q in enumerate(qs):
            if i in base_err:
                continue
            # deterministic thinning: every k-th query gets the oracles
            if fraction < 1.0 and (i % max(1, round(1 / fraction))):
                continue
            ex = live.rows
            if q.has("tlp_ok"):
                p = gen.partition_pred()
                d = ORC.tlp_check(ex, q, p.sql)
                note("tlp")
                if d is not None:
                    found("oracle-tlp", sc.name, f"p={p.sql}", q.sql(),
                          d, q=q, partition=p.sql)
                d = ORC.norec_check(ex, sc.table, p.sql, q.where)
                note("norec")
                if d is not None:
                    found("oracle-norec", sc.name, f"p={p.sql}",
                          q.sql(), d, q=q, partition=p.sql)
            if q.has("limited") and q.has("ordered"):
                d = ORC.limit_algebra_check(ex, q)
                note("limit")
                if d is not None:
                    found("oracle-limit", sc.name, "-", q.sql(), d,
                          q=q)
            if conn is not None and q.has("sqlite_ok") \
                    and not q.has("limited"):
                d = ORC.sqlite_check(ex, conn, q)
                note("sqlite")
                if d is not None:
                    found("oracle-sqlite", sc.name, "-", q.sql(), d,
                          q=q)
    finally:
        if conn is not None:
            conn.close()


def _run_canary_pair(sc, qs, base_rows, base_err, note, found,
                     pair_counts):
    """Replay the scenario with the padding canary armed: poisoned
    tails must change nothing, and the result/carry audits must stay
    silent."""
    from matrixone_tpu.utils import qa
    with qa.armed_scope(), qa.capture() as probe, \
            _pair_scope("canary"):
        live = LiveScenario(sc)
        try:
            for i, q in enumerate(qs):
                if i in base_err or not _applicable("canary", q):
                    continue
                try:
                    got = live.rows(q.sql())
                except Exception as e:  # noqa: BLE001 — lockstep error
                    # divergence (see _diff_one)
                    found("error-divergence", sc.name, "canary",
                          q.sql(), f"armed run raised {e!r}")
                    continue
                note("lockstep")
                pair_counts["canary"] = pair_counts.get("canary", 0) + 1
                d = ORC.diff_rows(base_rows[i], got, ordered=_ordered(q))
                if d is not None:
                    found("lockstep-mismatch", sc.name, "canary",
                          q.sql(), d, q=q)
        finally:
            live.close()
    for f in probe.findings():
        found(f.rule, sc.name, "canary", "-", f.format())


def _run_narrow_f32_drill(seed, note, found, pair_counts):
    """The documented-tolerance half of the narrow-encodings contract.

    The corpus scenarios carry no FLOAT32 column (doubles stay f64,
    decimals/counts stay scaled int64), so the lattice pair proves
    narrowing is LOSSLESS where the engine promises exactness — but
    never exercises the bf16 compute lane.  This drill builds a small
    f32 table whose values are deliberately bf16-INEXACT (mantissas
    longer than 8 bits), runs grouped float aggregates wide vs
    narrowed under the fused path, and holds the variant to the
    documented tolerance: group keys, counts and decimal sums compare
    EXACT; f32 sums/avgs/extrema within bf16 relative error (8
    mantissa bits -> ~0.4% per input; the drill's same-sign values
    keep sums from cancelling the error estimate away)."""
    import random

    rnd = random.Random(seed * 7919 + 13)
    vals = []
    for i in range(512):
        g = f"g{i % 7}"
        f = rnd.uniform(0.5, 2.0) + 1e-3 * rnd.random()
        q = rnd.randrange(0, 9999) / 100.0
        vals.append(f"({i}, '{g}', {f!r}, {q:.2f})")
    ddl = ("create table qa_nf (k bigint, g varchar(4), f float, "
           "q decimal(12,2))")
    ins = "insert into qa_nf values " + ", ".join(vals)
    sqls = (
        "select g, count(*) c, sum(q) sq, sum(f) sf, avg(f) af "
        "from qa_nf group by g order by g",
        "select g, sum(f) sf, min(f) mn, max(f) mx from qa_nf "
        "where k < 341 group by g order by g",
    )

    def run(narrow: bool):
        from matrixone_tpu.frontend import Session
        from matrixone_tpu.storage.engine import Engine
        env = dict(ENV_BASELINE)
        env.update({"MO_PLAN_FUSION": "1", "MO_FUSION_MIN_ROWS": "0"})
        if narrow:
            env["MO_NARROW_ENCODINGS"] = "1"
        out = []
        with env_scope(env):
            sess = Session(catalog=Engine())
            try:
                sess.execute(ddl)
                sess.execute(ins)
                for s in sqls:
                    out.append(sess.execute(s).rows())
            finally:
                sess.close()
        return out

    try:
        wide = run(False)
        slim = run(True)
    except Exception as e:  # noqa: BLE001 — an error on one side of a
        # lockstep pair IS the finding
        found("error-divergence", "narrow-f32", "narrow-encodings",
              "qa_nf drill", f"drill raised {e!r}")
        return
    for s, a, b in zip(sqls, wide, slim):
        note("narrow-f32")
        pair_counts["narrow-encodings"] = \
            pair_counts.get("narrow-encodings", 0) + 1
        d = ORC.diff_rows_close(a, b, rel=1e-2, abs_tol=1e-2)
        if d is not None:
            found("lockstep-mismatch", "narrow-f32",
                  "narrow-encodings", s, d)


def _run_mview_pair(sc, qs, base_rows, base_err, note, found,
                    pair_counts, max_views):
    """Commutation: insert-then-query ≡ query-over-materialized-view,
    under incremental maintenance and again after a full refresh."""
    cand = [(i, q) for i, q in enumerate(qs)
            if i not in base_err and _applicable("mview", q)]
    if not cand:
        return
    cand = cand[:max_views]
    with env_scope(ENV_BASELINE):
        live = LiveScenario(sc, waves=1)
        try:
            views = {}
            for i, q in cand:
                name = f"qa_mv_{i}"
                body = q.clone(order_by=[], limit=None, offset=None)
                try:
                    live.sess.execute(
                        f"create materialized view {name} as "
                        f"{body.sql()}")
                    views[i] = name
                except Exception as e:  # noqa: BLE001 — a shape the
                    # mview planner rejects is simply not applicable
                    continue
            live.insert_wave2()
            from matrixone_tpu.mview import catalog as vcat
            reg = vcat.registry_for(live.eng)
            for i, q in cand:
                if i not in views:
                    continue
                mode = reg[views[i]].mode if views[i] in reg else "full"
                for phase in ("incremental", "full"):
                    if phase == "incremental" and mode != "incremental":
                        # a full-mode view refreshes ON DEMAND by
                        # design (SHOW/EXPLAIN mark it); the
                        # insert-then-query commutation only binds
                        # after the refresh below
                        continue
                    if phase == "full":
                        live.ctl("mview", f"refresh:{views[i]}")
                    try:
                        got = live.rows(f"select * from {views[i]}")
                    except Exception as e:  # noqa: BLE001 — lockstep
                        # error divergence
                        found("error-divergence", sc.name, "mview",
                              q.sql(), f"{phase} read raised {e!r}")
                        break
                    note("mview")
                    pair_counts["mview"] = pair_counts.get(
                        "mview", 0) + 1
                    d = ORC.diff_rows(base_rows[i], got,
                                      ordered=False, tol_floats=True)
                    if d is not None:
                        found("lockstep-mismatch", sc.name,
                              f"mview-{phase}", q.sql(), d, q=q)
        finally:
            live.close()


def _run_stale_pair(sc, qs, base_err, note, found, pair_counts,
                    fraction):
    """Warm every cache layer, mutate the table, re-run: a cached plan,
    result, or compiled fragment that outlives the data it was built
    from returns plausible-but-wrong rows — exactly the PR-7 stale-LUT
    bug class.  Both phases run with the capture auditor ARMED
    (MO_KEY_AUDIT semantics, utils/keys.py): every rotate-rebuild
    lockstep also re-hashes the dictionary/constant content behind
    every fragment/plan-tree cache hit, so a weakened compile key
    surfaces as a `key-capture-mismatch` finding with both stacks even
    when the row diff happens to pass."""
    from matrixone_tpu.utils import keys as keyaudit
    cand = [(i, q) for i, q in enumerate(qs)
            if i not in base_err and _applicable("cache-stale", q)]
    step = max(1, round(1 / max(fraction, 1e-6)))
    cand = cand[::step]
    if not cand:
        return
    with _pair_scope("cache-stale"), keyaudit.armed_scope(), \
            keyaudit.capture() as kcap:
        live = LiveScenario(sc, waves=1, serving_off=False)
        try:
            live.ctl("serving", "result:on")
            for i, q in cand:        # warm: compile + fill caches
                try:
                    live.rows(q.sql())
                except Exception:  # noqa: BLE001 — baseline-rejected
                    # shapes were already reported; wave-1 data can
                    # also legitimately reject (e.g. empty vector set)
                    continue
            # the mutation: new rows AND string-content churn that
            # keeps dictionary LENGTHS stable (the stale-LUT trap)
            live.insert_wave2()
            mut = [m for m in (
                f"update {sc.table} set g = 'zq' where g = 'aa'",
                f"update {sc.table} set s = 'zz99' where s = 's00'",
            ) if any(c.name in ("g", "s") for c in sc.columns)]
            for m in mut:
                live.sess.execute(m)
            # truth: same engine, cold serving caches, unfused path
            live.ctl("serving", "clear")
            live.ctl("serving", "plan:off")
            live.ctl("serving", "result:off")
            with env_scope(ENV_BASELINE):
                truth = {}
                for i, q in cand:
                    try:
                        truth[i] = live.rows(q.sql())
                    except Exception:  # noqa: BLE001 — see warm loop
                        continue
            # warm re-run: caches + compiled fragments from BEFORE the
            # mutation must have been invalidated/re-keyed
            live.ctl("serving", "plan:on")
            live.ctl("serving", "result:on")
            for i, q in cand:
                if i not in truth:
                    continue
                try:
                    got = live.rows(q.sql())
                except Exception as e:  # noqa: BLE001 — lockstep error
                    # divergence
                    found("error-divergence", sc.name, "cache-stale",
                          q.sql(), f"post-mutation run raised {e!r}")
                    continue
                note("staleness")
                pair_counts["cache-stale"] = pair_counts.get(
                    "cache-stale", 0) + 1
                d = ORC.diff_rows(truth[i], got, ordered=_ordered(q))
                if d is not None:
                    found("cache-staleness", sc.name, "cache-stale",
                          q.sql(), d, q=q)
            # ---- phase 2: shape-preserving rebuild.  Same table,
            # same row COUNT and dictionary SIZES as the warm phase,
            # rotated string CONTENT: any compiled artifact keyed on
            # anything weaker than content (the PR-7 stale-LUT class)
            # now serves stale rows while every shape-based key
            # collides on purpose.
            from tools import moqa as _moqa
            wave1 = sc.rows[:sc.wave_split]
            live.sess.execute(f"drop table {sc.table}")
            live.sess.execute(sc.create_sql())
            if wave1:
                live.sess.execute(_moqa.rotate_insert_strings(
                    sc.insert_sql(wave1)))
            with env_scope(ENV_BASELINE):
                live.ctl("serving", "clear")
                live.ctl("serving", "plan:off")
                live.ctl("serving", "result:off")
                truth2 = {}
                for i, q in cand:
                    try:
                        truth2[i] = live.rows(q.sql())
                    except Exception:  # noqa: BLE001 — see warm loop
                        continue
                live.ctl("serving", "plan:on")
            for i, q in cand:
                if i not in truth2:
                    continue
                try:
                    got = live.rows(q.sql())
                except Exception as e:  # noqa: BLE001 — lockstep error
                    # divergence
                    found("error-divergence", sc.name, "cache-stale",
                          q.sql(), f"post-rebuild run raised {e!r}")
                    continue
                note("staleness")
                pair_counts["cache-stale"] = pair_counts.get(
                    "cache-stale", 0) + 1
                d = ORC.diff_rows(truth2[i], got, ordered=_ordered(q))
                if d is not None:
                    found("cache-staleness", sc.name, "cache-stale",
                          q.sql(), d, q=q)
            # ---- the capture auditor's verdict on both phases: a
            # mismatch here is a compile key that COLLIDED across the
            # mutation/rebuild — report it even when the row diff
            # passed (a zero-row or value-coincident query can mask
            # the stale program)
            for kf in kcap.findings():
                note("staleness")
                found("key-capture-mismatch", sc.name, "cache-stale",
                      f"{kf.site} capture {kf.name!r}", kf.detail)
        finally:
            live.close()


# ----------------------------------------------------------- last run

_LAST_RUN: Optional[dict] = None


def _remember(report: dict):
    global _LAST_RUN
    slim = dict(report)
    slim["findings_formatted"] = slim["findings_formatted"][:10]
    slim["findings"] = slim["findings"][:10]
    slim["ts"] = time.time()
    _LAST_RUN = slim


def last_run() -> Optional[dict]:
    return _LAST_RUN
