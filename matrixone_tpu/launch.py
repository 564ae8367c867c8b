"""Cluster launcher: one TOML file -> a whole running cluster.

Reference analogue: `cmd/mo-service -launch launch.toml`
(cmd/mo-service/launch.go:38 starts log -> TN -> CN in order from
per-role toml files; etc/launch/launch.toml). Redesign: one TOML
describes the deployment; the launcher spawns the log replicas, the TN
(journaling through the quorum WAL when replicas > 0), and N CN
processes (wired to each other's fragment endpoints for distributed
scopes), hosts the HAKeeper (+ optional standby) and the MySQL-aware
proxy in-process, points every service's heartbeats at the keepers, and
writes the port map to `<data_dir>/launch_ports.json` for tooling.

    [cluster]
    data_dir = "/var/lib/mo"      # shared storage for every role
    [log]
    replicas = 3                  # 0 = plain local WAL file
    [tn]
    port = 0                      # 0 = auto-assign
    [cn]
    count = 2
    insecure = true               # false = mo_user auth
    [keeper]
    enabled = true
    standby = true                # second keeper that takes over
    [proxy]
    enabled = true
    port = 0

Usage: `python -m matrixone_tpu.launch --launch cluster.toml` (stays in
the foreground like the reference binary; SIGTERM tears the tree down),
or programmatically: `Launcher(cfg_path).start() ... .stop()`.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
try:
    import tomllib                 # py311+
except ModuleNotFoundError:        # this image ships py310: use tomli
    import tomli as tomllib
from typing import Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_ports(n: int) -> List[int]:
    """n distinct free ports: every probe socket stays open until all
    are allocated, or the kernel may hand a just-released port out
    twice."""
    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


class Launcher:
    def __init__(self, cfg_path: str):
        with open(cfg_path, "rb") as f:
            self.cfg = tomllib.load(f)
        self.data_dir = self.cfg["cluster"]["data_dir"]
        os.makedirs(self.data_dir, exist_ok=True)
        self.procs: List[subprocess.Popen] = []
        self.ports: Dict[str, object] = {}
        self.keepers = []          # in-process HAKeeper objects
        self.proxy = None

    # ------------------------------------------------------------ spawn
    def _role_env(self, role: str) -> Dict[str, str]:
        """Environment of one child. An accelerator belongs to one
        process, so only CN roles get `[cluster] platform` ("cpu" by
        default, "tpu" for a chip deployment) and CN i is bound to chip
        i alone; log replicas, the TN and its respawns always start on
        the CPU backend. The launcher itself hosts only the keepers and
        the proxy and never initialises a jax backend."""
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        platform = self.cfg["cluster"].get("platform", "cpu")
        if not role.startswith("cn") or platform == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
            return env
        env["JAX_PLATFORMS"] = platform
        env["TPU_VISIBLE_CHIPS"] = role[2:]
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
        return env

    def _launch(self, mod: str, args: List[str], role: str):
        """Start a child; stderr goes to a per-role log under data_dir
        (a child that dies pre-PORT must leave a diagnostic)."""
        env = self._role_env(role)
        errlog = open(os.path.join(self.data_dir, f"{role}.stderr.log"),
                      "a")
        p = subprocess.Popen([sys.executable, "-m", mod] + args,
                             stdout=subprocess.PIPE, stderr=errlog,
                             env=env, text=True)
        errlog.close()               # the child holds its own fd now
        self.procs.append(p)
        return p

    @staticmethod
    def _collect_ports(p, mod: str, n_ports: int,
                       timeout_s: float = 180) -> List[int]:
        """Read the child's PORT lines under a REAL deadline: readline
        blocks, so it runs on a reaper thread joined with a timeout (a
        live-but-silent child must fail the launch, not hang it)."""
        got: List[int] = []

        def read():
            while len(got) < n_ports:
                line = p.stdout.readline()
                if not line:
                    return
                if line.startswith(("PORT ", "FRAGPORT ")):
                    got.append(int(line.split()[1]))
        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(timeout_s)
        if len(got) < n_ports:
            raise RuntimeError(f"{mod} did not report its ports "
                               f"(rc={p.poll()}; see its stderr log)")
        return got

    def _spawn(self, mod: str, args: List[str], role: str,
               n_ports: int = 1) -> List[int]:
        p = self._launch(mod, args, role)
        return self._collect_ports(p, mod, n_ports)

    def start(self) -> "Launcher":
        try:
            return self._start()
        except BaseException:       # noqa: BLE001 — incl.
            # KeyboardInterrupt mid-launch; re-raised after cleanup
            # a half-started cluster must not leak orphans holding the
            # ports and the data dir
            self.stop()
            raise

    def _start(self) -> "Launcher":
        # --- keepers first (services register as they come up)
        keeper_addrs = []
        if self.cfg.get("keeper", {}).get("enabled", False):
            from matrixone_tpu.hakeeper import HAKeeper
            state = os.path.join(self.data_dir, "keeper_state.json")

            def persist(snap, _p=state):
                # atomic: a crash mid-write must not corrupt membership
                # or the keeper-generation fencing state
                tmp = _p + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(snap, f)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, _p)

            def restore(_p=state):
                if not os.path.exists(_p):
                    return None
                with open(_p) as f:
                    return json.load(f)
            primary = HAKeeper(persist=persist, restore=restore).start()
            self.keepers.append(primary)
            keeper_addrs.append(f"127.0.0.1:{primary.port}")
            if self.cfg["keeper"].get("standby", False):
                standby = HAKeeper(
                    persist=persist, restore=restore,
                    standby_of=("127.0.0.1", primary.port)).start()
                self.keepers.append(standby)
                keeper_addrs.append(f"127.0.0.1:{standby.port}")
            self.ports["keepers"] = [k.port for k in self.keepers]
        keeper_opt = (["--keeper", ",".join(keeper_addrs)]
                      if keeper_addrs else [])

        # --- log replicas (launch.go: log service first) — started in
        # parallel within the tier; ports collected afterwards so the
        # tier costs ~one child init, not the sum
        n_rep = int(self.cfg.get("log", {}).get("replicas", 0))
        rep_procs = [
            self._launch("matrixone_tpu.logservice.replicated",
                         ["--dir", os.path.join(self.data_dir, f"log{i}"),
                          "--port", "0"], f"log{i}")
            for i in range(n_rep)]
        log_addrs = [
            f"127.0.0.1:{self._collect_ports(p, 'log replica', 1)[0]}"
            for p in rep_procs]
        self.ports["log"] = log_addrs

        # --- TN
        tn_args = ["--dir", self.data_dir, "--port",
                   str(self.cfg.get("tn", {}).get("port", 0))]
        if log_addrs:
            tn_args += ["--log-replicas", ",".join(log_addrs)]
        (tn_port,) = self._spawn("matrixone_tpu.cluster.tn",
                                 tn_args + keeper_opt, "tn")
        self.ports["tn"] = tn_port

        # --- TN failover (VERDICT r4 Next #9; reference:
        # hakeeper/checkers/tnservice): when the keeper marks the TN
        # DOWN, its repair hook respawns a TN over the same storage ON
        # THE SAME PORT — CN RPC clients and logtail consumers
        # reconnect by themselves, so nothing needs repointing. With
        # log replicas, the successor acquires the quorum WAL via
        # ELECTION (--campaign): it only proceeds once the dead
        # writer's lease lapses, and the replay of the quorum log
        # guarantees no acked commit is lost.
        if self.keepers and self.cfg.get("tn", {}).get(
                "auto_restart", True):
            respawn_args = (["--dir", self.data_dir,
                             "--port", str(tn_port)]
                            + (["--log-replicas", ",".join(log_addrs),
                                "--campaign"] if log_addrs else [])
                            + keeper_opt)

            def _respawn(_args=respawn_args):
                try:
                    p_ = self._launch("matrixone_tpu.cluster.tn",
                                      _args, "tn-respawn")
                    self._collect_ports(p_, "tn respawn", 1)
                except Exception as e:     # noqa: BLE001 — repair is
                    import sys as _sys     # best-effort; keeper records
                    print(f"[launch] TN respawn failed: {e}",
                          file=_sys.stderr, flush=True)

            def tn_repair(rec):
                # detach: the hook runs on the keeper's tick thread —
                # a slow respawn (port contention, quiet child) must
                # not stall failure detection for every other service
                threading.Thread(target=_respawn, daemon=True).start()
            for k in self.keepers:
                k.on_down("tn", tn_repair)

        # --- CNs (fragment endpoints pre-allocated so every CN knows
        # the full peer set at spawn time; spawned in parallel)
        cn_cfg = self.cfg.get("cn", {})
        n_cn = int(cn_cfg.get("count", 1))
        insecure = "1" if cn_cfg.get("insecure", True) else "0"
        frag_ports = _free_ports(n_cn)
        peers = ",".join(f"127.0.0.1:{p}" for p in frag_ports)
        cn_procs = [
            self._launch(
                "matrixone_tpu.cluster.cn",
                ["--tn", f"127.0.0.1:{tn_port}", "--dir", self.data_dir,
                 "--port", "0", "--frag-port", str(frag_ports[i]),
                 "--peers", peers, "--insecure", insecure] + keeper_opt,
                f"cn{i}")
            for i in range(n_cn)]
        cn_ports = [self._collect_ports(p, "cn", 2)[0]
                    for p in cn_procs]
        self.ports["cn"] = cn_ports
        self.ports["frag"] = frag_ports

        # --- proxy over the CNs
        if self.cfg.get("proxy", {}).get("enabled", False):
            from matrixone_tpu.frontend.proxy import MOProxy
            self.proxy = MOProxy(
                [("127.0.0.1", p) for p in cn_ports],
                port=int(self.cfg["proxy"].get("port", 0))).start()
            self.ports["proxy"] = self.proxy.port

        with open(os.path.join(self.data_dir, "launch_ports.json"),
                  "w") as f:
            json.dump(self.ports, f)
        return self

    def stop(self) -> None:
        if self.proxy is not None:
            self.proxy.stop()
        for k in self.keepers:
            k.stop()
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 5
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.wait(max(0.1, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    p.kill()


def main() -> None:
    import argparse
    import signal
    ap = argparse.ArgumentParser(prog="matrixone_tpu.launch")
    ap.add_argument("--launch", required=True, help="cluster TOML file")
    args = ap.parse_args()
    launcher = Launcher(args.launch).start()
    print(json.dumps(launcher.ports), flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    launcher.stop()


if __name__ == "__main__":
    main()
