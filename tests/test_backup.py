"""Physical backup/restore (reference: pkg/backup/tae.go — checkpoint
+ object copy with a verified file index; incremental by immutability)."""

import json
import os
import tempfile

from matrixone_tpu.frontend import Session
from matrixone_tpu.storage.engine import Engine
from matrixone_tpu.storage.fileservice import LocalFS
from matrixone_tpu.tools import backup as B


def _engine_with_data():
    d = tempfile.mkdtemp(prefix="mo_bak_src_")
    eng = Engine(LocalFS(d))
    s = Session(catalog=eng)
    s.execute("create table t (id bigint primary key, v varchar(8))")
    s.execute("insert into t values (1, 'a'), (2, 'b')")
    eng.checkpoint()
    s.execute("insert into t values (3, 'c')")   # WAL tail rides along
    return d, eng, s


def test_backup_restore_roundtrip():
    d, eng, s = _engine_with_data()
    bdir = tempfile.mkdtemp(prefix="mo_bak_dst_")
    out = B.cmd_backup(d, bdir)
    assert out["copied"] == out["files"] and out["skipped"] == 0
    assert B.cmd_verify(bdir)["ok"]

    rdir = tempfile.mkdtemp(prefix="mo_bak_rest_")
    r = B.cmd_restore(bdir, rdir)
    assert r["restored"] == out["files"]
    eng2 = Engine.open(LocalFS(rdir))
    s2 = Session(catalog=eng2)
    # checkpointed rows AND the WAL tail both restore
    assert sorted(x[0] for x in
                  s2.execute("select id from t").rows()) == [1, 2, 3]


def test_incremental_backup_skips_unchanged_objects():
    d, eng, s = _engine_with_data()
    bdir = tempfile.mkdtemp(prefix="mo_bak_inc_")
    first = B.cmd_backup(d, bdir)
    s.execute("insert into t values (4, 'd')")
    eng.checkpoint()                     # new segment object; old reused
    second = B.cmd_backup(d, bdir)
    assert second["skipped"] >= 1, second   # immutable objects skipped
    assert second["files"] > first["files"] - 1
    rdir = tempfile.mkdtemp(prefix="mo_bak_inc_r_")
    B.cmd_restore(bdir, rdir)
    s3 = Session(catalog=Engine.open(LocalFS(rdir)))
    assert sorted(x[0] for x in
                  s3.execute("select id from t").rows()) == [1, 2, 3, 4]


def test_verify_catches_corruption():
    d, eng, _ = _engine_with_data()
    bdir = tempfile.mkdtemp(prefix="mo_bak_cor_")
    B.cmd_backup(d, bdir)
    # corrupt one object in the backup
    idx = json.load(open(os.path.join(bdir, "backup_index.json")))
    obj = next(r for r in idx["files"] if r.startswith("objects/"))
    with open(os.path.join(bdir, obj), "ab") as f:
        f.write(b"CORRUPT")
    v = B.cmd_verify(bdir)
    assert not v["ok"] and v["corrupt"][0]["file"] == obj
    # restore refuses a corrupt backup
    r = B.cmd_restore(bdir, tempfile.mkdtemp())
    assert "error" in r


def test_backup_refuses_damaged_source_and_exit_codes():
    """code-review r5: missing referenced objects fail the backup
    loudly; verify failures exit nonzero from the CLI."""
    import subprocess
    import sys

    import pytest as _pt
    d, eng, _ = _engine_with_data()
    # damage the source: remove a referenced object
    idx = json.load(open(os.path.join(d, "meta", "manifest.json")))
    obj = idx["tables"]["t"]["objects"][0]["path"]
    os.remove(os.path.join(d, obj))
    with _pt.raises(SystemExit):
        B.cmd_backup(d, tempfile.mkdtemp())
    # CLI exit code 1 on a corrupt backup
    d2, eng2, _ = _engine_with_data()
    bdir = tempfile.mkdtemp()
    B.cmd_backup(d2, bdir)
    idx2 = json.load(open(os.path.join(bdir, "backup_index.json")))
    victim = next(r for r in idx2["files"] if r.startswith("objects/"))
    with open(os.path.join(bdir, victim), "ab") as f:
        f.write(b"X")
    r = subprocess.run(
        [sys.executable, "-m", "matrixone_tpu.tools.backup",
         "verify", bdir], capture_output=True, text=True,
        env={"PYTHONPATH": "/root/repo", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 1
