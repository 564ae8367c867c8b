"""Device-resident join / window / top-k fragments (vm/fusion_join.py,
vm/fusion_window.py, the fused topk terminal in vm/fusion.py): lockstep
bit-identicality against the per-operator path, the dispatch-count
contract for a Q3-shaped multi-join query, every degradation ladder
(kill-switches, duplicate fan-out, Grace spill, tiny batches), and the
batched build-side livesync regression (one motrace-counted host sync
per build finalize, not one per batch)."""

import datetime
import os

import numpy as np
import pytest

from matrixone_tpu.frontend import Session
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.utils import tpch
from matrixone_tpu.vm.compile import iter_ops


@pytest.fixture()
def env():
    keys = ("MO_PLAN_FUSION", "MO_FUSION_MIN_ROWS", "MO_FUSION_JOIN",
            "MO_FUSION_WINDOW", "MO_FUSION_TOPK")
    saved = {k: os.environ.get(k) for k in keys}
    yield os.environ
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


@pytest.fixture()
def sess(env):
    env["MO_FUSION_MIN_ROWS"] = "0"
    s = Session()
    s.execute("create table probe (id bigint primary key, k bigint,"
              " tag varchar(8), v bigint, d double)")
    rows = []
    for i in range(900):
        k = "NULL" if i % 11 == 7 else str(i % 40)
        rows.append(f"({i},{k},'t{i % 5}',{i % 100},{i % 13}.5)")
    s.execute(f"insert into probe values {', '.join(rows)}")
    s.execute("create table build (k bigint, name varchar(8), w bigint)")
    rows = []
    for i in range(180):
        k = "NULL" if i % 13 == 5 else str(i % 55)
        rows.append(f"({k},'n{i % 7}',{i})")
    s.execute(f"insert into build values {', '.join(rows)}")
    yield s
    s.close()


def _lockstep(s, sql):
    os.environ["MO_PLAN_FUSION"] = "0"
    r0 = s.execute(sql).rows()
    os.environ["MO_PLAN_FUSION"] = "1"
    r1 = s.execute(sql).rows()
    assert r0 == r1, f"fused differs for {sql!r}:\n{r0[:5]}\nvs\n{r1[:5]}"
    return r1


JOIN_QUERIES = [
    # numeric keys with NULLs and duplicate fan-out on both sides
    "select probe.id, build.w from probe join build on probe.k = build.k"
    " order by probe.id, build.w",
    "select probe.id, build.name from probe left join build"
    " on probe.k = build.k order by probe.id, build.name",
    "select id from probe where exists"
    " (select 1 from build where build.k = probe.k) order by id",
    "select id from probe where not exists"
    " (select 1 from build where build.k = probe.k) order by id",
    # dict-string key: the two sides' dictionaries assign codes
    # independently — the probe-side translation LUT path
    "select probe.id, build.w from probe join build"
    " on probe.tag = build.name order by probe.id, build.w",
    # residual ON predicate filtering match lanes pre-null-extension
    "select probe.id, build.w from probe left join build"
    " on probe.k = build.k and build.w > 60"
    " order by probe.id, build.w",
    # the fused probe->filter->project->agg chain
    "select build.name, sum(probe.v) s, count(*) n from probe"
    " join build on probe.k = build.k where probe.d > 1.0"
    " group by build.name order by build.name",
]


def test_join_fragment_lockstep(sess):
    for sql in JOIN_QUERIES:
        _lockstep(sess, sql)


def test_join_fragment_lockstep_multi_batch(sess):
    sess.execute("set batch_rows = 128")
    try:
        for sql in JOIN_QUERIES[:4]:
            _lockstep(sess, sql)
    finally:
        sess.execute("set batch_rows = 0")


def test_join_kill_switches_bit_identical(sess, env):
    sql = JOIN_QUERIES[-1]
    want = _lockstep(sess, sql)
    for knob in ("MO_FUSION_JOIN", "MO_FUSION_TOPK",
                 "MO_FUSION_WINDOW"):
        env[knob] = "0"
        assert sess.execute(sql).rows() == want, knob
        env.pop(knob, None)


def test_kill_switch_invalidates_cached_tree(env):
    """The kill-switches are baked into the compiled tree, so they must
    ride the plan-cache tree signature: warm a fused-join tree, flip
    MO_FUSION_JOIN=0, and the SAME statement must rebuild onto the
    barrier path instead of serving the cached fused tree."""
    from matrixone_tpu.utils import metrics as M
    env["MO_FUSION_MIN_ROWS"] = "0"
    s = Session()
    try:
        s.execute("create table kt (k bigint, v bigint)")
        s.execute("create table kd (k bigint, w bigint)")
        s.execute("insert into kt values " + ",".join(
            f"({i % 7},{i})" for i in range(300)))
        s.execute("insert into kd values " + ",".join(
            f"({j},{j * 3})" for j in range(7)))
        sql = ("select kd.w, sum(kt.v) s from kt join kd on kt.k = kd.k"
               " group by kd.w order by s limit 3")
        want = s.execute(sql).rows()
        s.execute(sql)                       # warm the cached tree
        f0 = M.fusion_exec.get(mode="fused")
        assert s.execute(sql).rows() == want
        assert M.fusion_exec.get(mode="fused") > f0, \
            "premise: the warm statement runs the fused join"
        env["MO_FUSION_JOIN"] = "0"
        f1 = M.fusion_exec.get(mode="fused")
        assert s.execute(sql).rows() == want
        assert M.fusion_exec.get(mode="fused") == f1, \
            "MO_FUSION_JOIN=0 must invalidate the cached fused tree"
        env.pop("MO_FUSION_JOIN", None)
    finally:
        s.close()


def test_duplicate_fanout_doubles_lanes_fused(sess):
    """Past max_matches duplicates the fused probe re-runs the SAME
    batch with doubled lanes — one extra dispatch, identical rows."""
    sess.execute("create table dup (k bigint, x bigint)")
    rows = ",".join(f"({i % 3},{i})" for i in range(60))
    sess.execute(f"insert into dup values {rows}")
    _lockstep(sess, "select probe.id, dup.x from probe join dup"
                    " on probe.k = dup.k order by probe.id, dup.x")


def test_grace_spill_ladder_untouched(sess):
    """A build side past join_build_budget falls off the fused path
    onto the ORIGINAL JoinOp's Grace spill — bit-identical rows and
    the spill counter ticks."""
    sql = ("select probe.id, build.w from probe join build"
           " on probe.k = build.k order by probe.id, build.w")
    want = _lockstep(sess, sql)
    before = M.join_spills.get()
    sess.variables["join_build_budget"] = 64
    try:
        os.environ["MO_PLAN_FUSION"] = "1"
        assert sess.execute(sql).rows() == want
    finally:
        sess.variables.pop("join_build_budget", None)
    assert M.join_spills.get() > before


def test_semi_anti_over_swapped_join_stream(sess):
    """Regression (tpch q21): a CBO side swap makes the join node's
    declared schema order differ from the probe chain's physical
    column order — the fused semi/anti stream payload must map columns
    by the CHAIN's order, not the node's, or every downstream name
    reads another column's data."""
    sess.execute("create table nat (nk bigint, nname varchar(12))")
    sess.execute("insert into nat values (1,'alpha'),(2,'beta')")
    sql = ("select count(*) c from build, probe, nat"
           " where build.k = probe.k and build.w % 2 = nk"
           " and nname = 'alpha'"
           " and exists (select 1 from probe p2 where p2.k = probe.k"
           "             and p2.id <> probe.id)"
           " and not exists (select 1 from probe p3 where"
           "             p3.k = probe.k and p3.v > probe.v)")
    _lockstep(sess, sql)


WINDOW_QUERIES = [
    "select id, row_number() over (partition by tag order by v, id) rn"
    " from probe order by id",
    "select id, rank() over (partition by tag order by v) rk,"
    " dense_rank() over (order by v) dr from probe order by id",
    "select id, sum(v) over (partition by tag) s,"
    " count(*) over (partition by tag) n from probe order by id",
    "select id, ntile(4) over (order by id) nt from probe order by id",
    # window output feeding a fused filter/project tail
    "select id, rk from (select id, rank() over (partition by tag"
    " order by v) rk from probe) q where rk <= 3 order by id",
]


def test_window_fragment_lockstep(sess):
    from matrixone_tpu.vm.fusion_window import FusedWindowOp
    for sql in WINDOW_QUERIES:
        _lockstep(sess, sql)
    # the plan actually forms a window fragment
    os.environ["MO_PLAN_FUSION"] = "1"
    from matrixone_tpu.sql.binder import Binder
    from matrixone_tpu.sql.parser import parse
    from matrixone_tpu.vm.compile import compile_plan
    sel = parse(WINDOW_QUERIES[0])[0]
    sess._prepare_select(sel)
    node = Binder(sess.catalog).bind_statement(sel)
    node = sess._cbo(node)
    op = compile_plan(node, sess._ctx())
    assert [o for o in iter_ops(op) if isinstance(o, FusedWindowOp)]


def test_framed_windows_stay_barriers(sess):
    """Framed aggregates and value functions are NOT fusable — they
    run per-operator and stay lockstep-correct with fusion on."""
    for sql in (
        "select id, sum(v) over (partition by tag order by id rows"
        " between 1 preceding and current row) s from probe order by id",
        "select id, lag(v) over (partition by tag order by id) l"
        " from probe order by id",
    ):
        _lockstep(sess, sql)


TOPK_QUERIES = [
    "select v, d from probe where v is not null order by d, v limit 7",
    "select v, d from probe order by v desc, d limit 5 offset 4",
    # heavy ties: the fused carry's (keys, global row index) total
    # order must reproduce the host path's stable-sort tiebreak
    "select k, v from probe order by k limit 9",
    "select id, v from probe order by v desc limit 100",
]


def test_topk_fused_terminal_lockstep(sess):
    for sql in TOPK_QUERIES:
        _lockstep(sess, sql)


def test_topk_fused_terminal_multi_batch(sess):
    sess.execute("set batch_rows = 128")
    try:
        for sql in TOPK_QUERIES:
            _lockstep(sess, sql)
    finally:
        sess.execute("set batch_rows = 0")


def test_q3_shape_dispatch_bound_and_oracle(env):
    """THE acceptance contract: a Q3-shaped join+agg+topk query runs
    warm in <= 4 compiled dispatches per probe batch (asserted via
    mo_fusion_dispatch_total), with rows exactly equal to the integer-
    domain oracle and to the unfused path."""
    env["MO_FUSION_MIN_ROWS"] = "0"
    s = Session()
    try:
        # pin the scan batch size so the probe side REALLY spans
        # multiple batches (the session default of 1<<20 would emit one
        # batch and make the per-batch bound below trivially slack)
        batch_rows = 8192
        s.execute(f"set batch_rows = {batch_rows}")
        arrays = tpch.load_lineitem(s.catalog, 20_000, seed=2)
        q3data = tpch.load_tpch_q3(s.catalog, 4_000, seed=2)
        os.environ["MO_PLAN_FUSION"] = "0"
        base = s.execute(tpch.Q3_SQL).rows()
        os.environ["MO_PLAN_FUSION"] = "1"
        s.execute(tpch.Q3_SQL)                  # trace + compile
        d0 = M.fusion_dispatch.get(kind="step")
        e0 = M.fusion_dispatch.get(kind="eager")
        got = s.execute(tpch.Q3_SQL).rows()     # warm
        steps = M.fusion_dispatch.get(kind="step") - d0
        assert M.fusion_dispatch.get(kind="eager") == e0, \
            "warm Q3 must not fall off the compiled path"
        assert got == base
        # oracle exactness (same check as test_tpch.test_q3_exact)
        exp = tpch.q3_oracle(arrays, q3data)
        assert len(got) == len(exp)
        epoch = datetime.date(1970, 1, 1)
        for g, e in zip(got, exp):
            assert g[0] == e[0]
            assert round(g[1] * 10000) == e[1]
            assert (g[2] - epoch).days == e[2]
        # lineitem 20k rows at the pinned batch size -> 3 probe
        # batches; bound the budget per PROBE batch at 4 —
        # per-operator execution needs >= 10
        n_batches = max(1, -(-20_000 // batch_rows))
        assert n_batches == 3
        assert steps / n_batches <= 4, (steps, n_batches)
    finally:
        s.close()


def _mask_batch(padded: int, live: int):
    import jax.numpy as jnp

    from matrixone_tpu.container.device import DeviceBatch
    from matrixone_tpu.vm.exprs import ExecBatch
    mask = jnp.arange(padded, dtype=jnp.int32) < live
    db = DeviceBatch(columns={}, n_rows=jnp.asarray(live, jnp.int32))
    return ExecBatch(batch=db, dicts={}, mask=mask)


def _livesync_spans(batches, budget):
    from matrixone_tpu.utils import motrace
    from matrixone_tpu.vm import join as J
    was_armed, was_sample = motrace.TRACER.armed, motrace.TRACER.sample
    motrace.TRACER.arm(sample=1.0)
    motrace.TRACER.clear()
    try:
        with motrace.root_span("livesync-test"):
            got, overflowed = J.stream_build_side(iter(batches), budget)
        spans = []
        for tid in motrace.TRACER.trace_ids():
            spans += [sp for sp in motrace.TRACER.spans_of(tid)
                      if sp["name"] == "join.build.livesync"]
        return got, overflowed, spans
    finally:
        motrace.TRACER.armed = was_armed
        motrace.TRACER.sample = was_sample
        motrace.TRACER.clear()


def test_build_livesync_one_sync_per_finalize():
    """Regression for the per-batch device_get in the build-side live
    counter: a heavily masked build side streaming many batches past
    the padded bound drains its pending mask-sums in O(1) fused
    reductions (motrace `join.build.livesync` spans), not one sync per
    batch (the pre-refactor behavior: every batch past the bound)."""
    # 30 batches, 64 padded lanes each, only 2 live rows per batch:
    # the padded upper bound crosses budget=1000 at batch 16, but the
    # coalesced drain proves live=32 and resets — ONE sync, where the
    # old per-batch device_get would have synced ~15 times
    batches = [_mask_batch(64, 2) for _ in range(30)]
    got, overflowed, spans = _livesync_spans(batches, 1000)
    assert len(got) == 30 and not overflowed
    assert len(spans) == 1, [sp["attrs"] for sp in spans]
    assert spans[0]["attrs"]["pending"] == 16
    # a build side that actually fits its padded bound never syncs
    got, overflowed, spans = _livesync_spans(
        [_mask_batch(64, 64) for _ in range(4)], 1000)
    assert len(got) == 4 and not overflowed and not spans
    # a genuinely over-budget build overflows on the FIRST drain
    got, overflowed, spans = _livesync_spans(
        [_mask_batch(64, 64) for _ in range(30)], 1000)
    assert overflowed and len(spans) == 1


def _hashes(rng, n):
    return rng.integers(0, 1 << 63, size=n, dtype=np.uint64)


def _dup_run(rng):
    run = np.sort(np.concatenate([                  # a run over 1,024
        np.full(900, _hashes(rng, 1)[0]), _hashes(rng, 1500),
        np.full(4, np.uint64(0xFFFFFFFFFFFFFFFF))]))   # NULL-hash sentinel
    return run, np.concatenate([
        rng.choice(run, 700), _hashes(rng, 300),
        np.array([0, 1, 0xFFFFFFFFFFFFFFFF], np.uint64)])


def _straddle(_rng):
    s = np.array([(1 << 63) - 2, (1 << 63) - 1, 1 << 63, (1 << 63) + 1],
                 dtype=np.uint64)
    return s, np.concatenate([s, s + np.uint64(1), np.zeros(1, np.uint64)])


def _present(rng):
    srt = np.sort(_hashes(rng, 1100))
    return srt, srt[::3]


# name -> rng -> (sorted build hashes, probe hashes)
_PROBE_ENTRY_CASES = {
    "empty_build": lambda r: (_hashes(r, 0), _hashes(r, 7)),
    "all_equal_build": lambda r: (
        np.full(2500, 77, np.uint64),
        np.array([0, 76, 77, 78], np.uint64)),
    "under_min_and_over_max": lambda r: (
        np.sort(_hashes(r, 1100) | np.uint64(1 << 20)) >> np.uint64(1),
        np.array([0, 1, (1 << 64) - 1, (1 << 64) - 2], np.uint64)),
    "either_side_of_2_63": _straddle,
    "lengths_off_1024": lambda r: (np.sort(_hashes(r, 2500)),
                                   _hashes(r, 1003)),
    "duplicate_run_straddles_1024": _dup_run,
    "present_keys": _present,
    "int32_at_sf1_orders_x_2_20": lambda r: (
        np.sort(_hashes(r, 1_500_000)), _hashes(r, 1 << 20)),
}


@pytest.mark.parametrize("case", sorted(_PROBE_ENTRY_CASES))
def test_probe_entry_is_searchsorted_left(case):
    """What `expand_probe` relies on for its entry into the sorted hash
    run: `jnp.searchsorted(..).astype(int32)` over uint64 hashes is
    numpy's searchsorted-left (the inputs the deleted Pallas sorted
    search was held to)."""
    import jax
    import jax.numpy as jnp
    srt, q = _PROBE_ENTRY_CASES[case](np.random.default_rng(31))
    got = jax.jit(lambda s, p: jnp.searchsorted(s, p).astype(jnp.int32))(
        jnp.asarray(srt), jnp.asarray(q))
    assert got.dtype == jnp.int32
    want = np.searchsorted(srt, q, side="left")
    assert np.array_equal(np.asarray(got).astype(np.int64), want)
