"""Offline inspector CLI (reference: cmd/mo-inspect + mo-object-tool +
VIEW_CKP_STATUS.md ops surface)."""

import json
import subprocess
import sys
import tempfile

from matrixone_tpu.frontend import Session
from matrixone_tpu.storage.engine import Engine
from matrixone_tpu.storage.fileservice import LocalFS
from matrixone_tpu.tools import inspect as I


def _mkdir_engine():
    d = tempfile.mkdtemp(prefix="mo_inspect_")
    eng = Engine(LocalFS(d))
    s = Session(catalog=eng)
    s.execute("create table t (id bigint primary key, v bigint,"
              " s varchar(8))")
    s.execute("insert into t values (1, 10, 'a'), (2, 20, 'b')")
    s.execute("insert into t values (3, 30, 'c')")
    s.execute("delete from t where id = 2")
    eng.checkpoint()
    s.execute("insert into t values (4, 40, 'd')")   # WAL tail
    return d, eng


def test_inspect_api():
    d, eng = _mkdir_engine()
    fs = LocalFS(d)
    m = I.cmd_manifest(fs)
    assert "t" in m["tables"]
    t = I.cmd_tables(fs)["t"]
    assert t["rows_in_objects"] == 3 and t["tombstoned_rows"] == 1
    assert t["live_rows_at_ckpt"] == 2
    objs = I.cmd_objects(fs, d)["t"]
    assert len(objs) == 2 and all(o["bytes_on_disk"] > 0 for o in objs)
    ob = I.cmd_object(fs, objs[0]["path"])
    assert ob["format_version"] == 2
    assert set(ob["columns"]) == {"id", "v", "s"}
    assert ob["zonemaps"]["id"]["min"] == 1
    w = I.cmd_wal(fs)
    assert w["records"] >= 1                        # the post-ckpt insert
    st = I.cmd_status(fs, d)
    assert st["checkpointed"] and st["objects"] == 2
    assert st["object_bytes"] > 0


def test_inspect_cli_process():
    d, _ = _mkdir_engine()
    out = subprocess.run(
        [sys.executable, "-m", "matrixone_tpu.tools.inspect",
         "status", d],
        capture_output=True, text=True,
        env={"PYTHONPATH": "/root/repo", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    st = json.loads(out.stdout)
    assert st["checkpointed"] is True and st["tables"] >= 1


def test_inspect_empty_dir():
    d = tempfile.mkdtemp(prefix="mo_inspect_empty_")
    assert "error" in I.cmd_manifest(LocalFS(d))
    assert I.cmd_status(LocalFS(d), d)["checkpointed"] is False
