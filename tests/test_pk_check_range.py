"""The commit's key check reads the key columns only of the segments whose
range of the key's first column holds a new key (storage/engine.py
`_key_range_excludes`): an append past every loaded key fetches nothing,
and a key inside a range is still refused."""

import numpy as np
import pytest

from matrixone_tpu.frontend.session import Session
from matrixone_tpu.storage.engine import DuplicateKeyError, Engine
from matrixone_tpu.storage.fileservice import LocalFS

KEYS = {"single": "k bigint primary key, j bigint",
        "composite": "k bigint, j bigint, primary key (k, j)"}


class _EveryKeyASuspect:
    """A bloom that answers `maybe` for every key: the exact check runs."""

    def probe_int64(self, keys):
        return np.ones(len(keys), bool)

    def add_int64(self, keys):
        pass


def _table(tmp_path, key, reopen):
    eng = Engine(LocalFS(str(tmp_path)))
    s = Session(eng)
    s.execute(f"create table t ({KEYS[key]}, v bigint)")
    for lo in (0, 100, 200):                      # three commits
        s.execute("insert into t values " + ", ".join(
            f"({k}, {k % 3}, 0)" for k in range(lo, lo + 100)))
    if reopen:                                    # object-backed, lazy
        eng.checkpoint()
        eng.close()
        eng = Engine.open(LocalFS(str(tmp_path)))
        s = Session(eng)
    t = eng.get_table("t")
    t._pk_bloom = _EveryKeyASuspect()
    t._pk_bloom_cap, t._pk_bloom_items = 1 << 30, 0
    hashed = []
    inner = t.pk_key_values
    t.pk_key_values = lambda arrays: (hashed.append(len(arrays["k"])),
                                      inner(arrays))[1]
    return s, t, hashed


@pytest.mark.parametrize("reopen", [False, True], ids=["ram", "objects"])
@pytest.mark.parametrize("key", sorted(KEYS))
def test_an_append_past_the_loaded_keys_reads_no_segment(tmp_path, key,
                                                         reopen):
    s, t, hashed = _table(tmp_path, key, reopen)
    s.execute("insert into t values (300, 0, 1), (301, 1, 1)")
    assert 100 not in hashed                      # no loaded segment's keys
    assert s.execute("select count(*) from t").rows()[0][0] == 302


@pytest.mark.parametrize("reopen", [False, True], ids=["ram", "objects"])
@pytest.mark.parametrize("key", sorted(KEYS))
def test_a_key_inside_a_segments_range_is_still_refused(tmp_path, key,
                                                        reopen):
    s, t, hashed = _table(tmp_path, key, reopen)
    with pytest.raises(DuplicateKeyError):
        s.execute("insert into t values (400, 0, 1), (150, 0, 1)")
    # only the segment that holds 100..199 was read, beside the batch
    assert hashed.count(100) == 1
    # composite: the same first column with another second one is new
    if key == "composite":
        s.execute("insert into t values (150, 7, 1)")
    # a deleted key inside a range is free again
    s.execute("delete from t where k = 250")
    s.execute("insert into t values (250, 1, 2)")
    assert s.execute("select v from t where k = 250").rows() == [(2,)]
