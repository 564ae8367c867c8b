"""Launch-file cluster composition (L0 gap; reference:
cmd/mo-service/launch.go:38 + etc/launch/launch.toml): one TOML brings
up log replicas, a TN journaling through the quorum WAL, N CNs with
distributed-scope wiring, keepers, and the proxy — and SQL flows through
the whole tree.
"""

import json
import os
import tempfile
import time

import pytest

from matrixone_tpu import client
from matrixone_tpu.launch import Launcher


@pytest.fixture(scope="module")
def cluster():
    d = tempfile.mkdtemp(prefix="mo_launch_")
    cfg = os.path.join(d, "cluster.toml")
    with open(cfg, "w") as f:
        f.write(f"""
[cluster]
data_dir = "{d}/data"
[log]
replicas = 3
[tn]
port = 0
[cn]
count = 2
insecure = true
[keeper]
enabled = true
standby = true
[proxy]
enabled = true
port = 0
""")
    launcher = Launcher(cfg).start()
    yield d, launcher
    launcher.stop()


def test_toml_launch_end_to_end(cluster):
    d, launcher = cluster
    ports = launcher.ports
    assert len(ports["log"]) == 3
    assert len(ports["cn"]) == 2
    assert len(ports["keepers"]) == 2
    # port map persisted for tooling
    with open(os.path.join(d, "data", "launch_ports.json")) as f:
        assert json.load(f)["tn"] == ports["tn"]

    # SQL through the proxy lands on some CN; replication reaches both
    c = client.connect(port=ports["proxy"], timeout=120)
    c.execute("create table lt (id bigint primary key, v varchar(16))")
    c.execute("insert into lt values (1, 'from-proxy'), (2, 'x')")
    for cn_port in ports["cn"]:
        cc = client.connect(port=cn_port, timeout=120)
        deadline = time.time() + 30
        while time.time() < deadline:
            _cols, rows = cc.query("select id, v from lt order by id")
            if len(rows) == 2:
                break
            time.sleep(0.2)
        assert [(int(a), b) for a, b in rows] == [(1, "from-proxy"),
                                                 (2, "x")]


def test_launch_wires_quorum_wal(cluster):
    """The TN really journals through the spawned log replicas: each
    replica's file holds the committed records."""
    d, launcher = cluster
    import glob
    logs = sorted(glob.glob(os.path.join(d, "data", "log*",
                                         "replica.log")))
    assert len(logs) == 3
    time.sleep(0.5)
    nonempty = sum(1 for p in logs if os.path.getsize(p) > 0)
    assert nonempty >= 2, "quorum WAL files empty — TN not journaling"


def test_launch_registers_heartbeats(cluster):
    d, launcher = cluster
    from matrixone_tpu.hakeeper import details_via_tcp
    addrs = [("127.0.0.1", p) for p in launcher.ports["keepers"]]
    deadline = time.time() + 15
    kinds = {}
    while time.time() < deadline:
        svcs = details_via_tcp(addrs)
        kinds = {}
        for s in svcs:
            kinds.setdefault(s["kind"], []).append(s["state"])
        if len(kinds.get("cn", [])) == 2 and kinds.get("tn"):
            break
        time.sleep(0.3)
    assert len(kinds.get("cn", [])) == 2 and len(kinds.get("tn", [])) == 1
    assert all(st == "up" for sts in kinds.values() for st in sts)


@pytest.mark.slow
def test_tn_kill9_failover_no_acked_loss():
    """VERDICT r4 Next #9 drill: kill -9 the TN in a launched cluster;
    the keeper's repair hook respawns a TN on the same port, which wins
    the quorum-WAL election once the dead writer's lease lapses and
    replays every acked commit; CN sessions resume writing.

    Marked slow: a 15s multi-process kill/elect/replay drill (this whole
    module was absent from tier-1 until the py310 tomllib fix — the four
    fast launch tests now run there, this drill rides the slow lane)."""
    import signal
    import subprocess

    d = tempfile.mkdtemp(prefix="mo_launch_fo_")
    cfg = os.path.join(d, "cluster.toml")
    with open(cfg, "w") as f:
        f.write(f"""
[cluster]
data_dir = "{d}/data"
[log]
replicas = 3
[tn]
port = 0
[cn]
count = 1
insecure = true
[keeper]
enabled = true
""")
    launcher = Launcher(cfg).start()
    try:
        cn_port = launcher.ports["cn"][0]
        c = client.connect(port=cn_port, timeout=240.0)
        c.execute("create table acc (id bigint primary key, v bigint)")
        for i in range(12):
            c.execute(f"insert into acc values ({i}, {i * 10})")

        # find the TN child and kill -9 it mid-stream
        tn_proc = None
        for p in launcher.procs:
            if "matrixone_tpu.cluster.tn" in " ".join(p.args):
                tn_proc = p
        assert tn_proc is not None
        tn_proc.send_signal(signal.SIGKILL)
        tn_proc.wait(timeout=10)

        # keeper detects + respawns; writes resume through the SAME CN
        deadline = time.time() + 120
        resumed = False
        while time.time() < deadline:
            try:
                c.execute("insert into acc values (100, 1000)")
                resumed = True
                break
            except Exception:
                time.sleep(1.0)
                try:
                    c.close()
                except Exception:
                    pass
                c = client.connect(port=cn_port, timeout=240.0)
        assert resumed, "writes never resumed after TN kill -9"
        _, rows = c.query("select count(*), sum(v) from acc")
        n, sv = int(rows[0][0]), int(rows[0][1])
        # every acked pre-kill commit survived + the post-failover row
        assert n == 13 and sv == sum(i * 10 for i in range(12)) + 1000
        # keeper recorded the repair
        ops = [o for k in launcher.keepers for o in k.operators
               if o.get("kind") == "tn"]
        assert any(o.get("repair") == "dispatched" for o in ops), ops
        c.close()
    finally:
        launcher.stop()


def test_dashboard_snapshot(cluster):
    """mo-dashboard role: one poll over a launched cluster reports
    every role healthy."""
    from matrixone_tpu.tools import dashboard
    d, launcher = cluster
    snap = dashboard.snapshot(f"{d}/data")
    assert snap["tn"]["ok"] and "committed_ts" in snap["tn"]
    assert len(snap["log"]) == 3 and all(r["ok"] for r in snap["log"])
    assert len(snap["cn_fragments"]) == 2
    assert all("frags_run" in c for c in snap["cn_fragments"])
    kinds = {s["kind"] for s in snap["services"]}
    assert {"tn", "cn"} <= kinds


# ---- the chip belongs to one process: what each role's child is given

def _launcher_for(tmp_path, platform):
    cfg = tmp_path / "cluster.toml"
    plat = f'platform = "{platform}"\n' if platform else ""
    cfg.write_text(f'[cluster]\ndata_dir = "{tmp_path}/data"\n{plat}')
    return Launcher(str(cfg))


@pytest.mark.parametrize("role", ["log0", "log2", "tn", "tn-respawn"])
def test_chip_deployment_keeps_non_cn_roles_on_cpu(tmp_path, role,
                                                   monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")   # whatever the parent has
    env = _launcher_for(tmp_path, "tpu")._role_env(role)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in env


@pytest.mark.parametrize("i", [0, 3])
def test_chip_deployment_gives_each_cn_one_chip(tmp_path, i):
    env = _launcher_for(tmp_path, "tpu")._role_env(f"cn{i}")
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_CHIPS"] == str(i)
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"


@pytest.mark.parametrize("role", ["log0", "tn", "cn0", "cn1"])
def test_default_deployment_is_cpu_for_every_role(tmp_path, role):
    env = _launcher_for(tmp_path, None)._role_env(role)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in env


def test_launcher_process_initialises_no_jax_backend(tmp_path):
    """The launcher hosts the keepers and the proxy in-process; neither
    may take a device (a parent that holds the chip starves its CNs)."""
    import subprocess
    import sys
    code = (
        "import jax._src.xla_bridge as xb\n"
        "from matrixone_tpu.launch import Launcher\n"
        "from matrixone_tpu.hakeeper import HAKeeper\n"
        "from matrixone_tpu.frontend.proxy import MOProxy\n"
        "k = HAKeeper().start(); p = MOProxy([('127.0.0.1', 1)]).start()\n"
        "p.stop(); k.stop()\n"
        "assert not xb._backends, list(xb._backends)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
