"""The general traffic generator: the same seed gives the same statements,
whatever its size; parameters stay inside the ranges the mix states."""

import re

import traffic

CFG = {"nprobe": 8, "k": 20}


def test_same_seed_same_statements_and_large_seeds():
    mix = traffic.load_mix("scan-agg")
    a = traffic.generate(mix, CFG, {}, 2**31 + 12345)
    b = traffic.generate(mix, CFG, {}, 2**31 + 12345)
    c = traffic.generate(mix, CFG, {}, 7)
    assert a == b and a["statements"] != c["statements"]
    assert len(a["statements"]) == mix["statements"]
    assert a["clients"] == 1 and a["starts"] == [0]


def test_q1_q6_alternate_with_parameters_in_range():
    plan = traffic.generate(traffic.load_mix("scan-agg"), CFG, {}, 11)
    for j, (sql, meta) in enumerate(zip(plan["statements"], plan["meta"])):
        assert meta["template"] == ("q1", "q6")[j % 2]
        assert meta["tables"] == ["lineitem"] and "{" not in sql
        p = meta["params"]
        if meta["template"] == "q1":
            assert 60 <= p["delta"] <= 120
            assert f"interval '{p['delta']}' day" in sql
        else:
            assert 1993 <= p["year"] <= 1997 and 2 <= p["discount"] <= 9
            assert p["quantity"] in (24, 25)
            lo, hi = re.search(r"l_discount >= (\S+) and l_discount <= (\S+)",
                               sql).groups()
            assert (lo, hi) == (f"0.{p['discount'] - 1:02d}",
                                f"0.{p['discount'] + 1:02d}")
            assert f"date '{p['year']}-01-01'" in sql


def test_pool_traffic_cycles_from_each_clients_own_offset():
    pools = {"queries": [f"[{i}.0]" for i in range(300)]}
    plan = traffic.generate(traffic.load_mix("search-c100"), CFG, pools, 3)
    assert plan["clients"] == 100 and len(plan["statements"]) == 300
    assert plan["starts"] == [3 * i for i in range(100)]
    assert plan["session"] == ["set ivf_nprobe = 8"]
    assert plan["statements"][5] == ("select id from docs order by "
                                     "l2_distance(v, '[5.0]') limit 20")
    one = traffic.generate(traffic.load_mix("search-c1"), CFG, pools, 3)
    assert one["clients"] == 1 and one["statements"] == plan["statements"]
