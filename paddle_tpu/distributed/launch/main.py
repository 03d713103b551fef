"""Multi-process launcher with watchdog + elastic restart.

Reference call stack (SURVEY.md §3.3):
  python -m paddle.distributed.launch --devices ... train.py
    -> launch/main.py — launch() -> context (args+env)
    -> controllers/collective.py — CollectiveController.build_job
         rendezvous -> PADDLE_TRAINER_ENDPOINTS / PADDLE_TRAINER_ID ...
    -> job/container.py — Container.start (Popen per device)
    -> controller.watch(): on failure & elastic -> kill all, restart
       (fleet/elastic/manager.py — ElasticManager, max_restart)

TPU-native deltas (documented, deliberate):
  * one process per HOST (jax single-controller drives all local chips).
    ``--nproc_per_node`` > 1 is for CPU SIMULATION ONLY, each process
    with its own virtual devices: a chip belongs to one process at a
    time, so two workers on a chip host contend for the same chips and
    fail or hang.  This parent never touches ``jax.devices()`` — a
    parent that had would hold the chip its one child needs.
  * rendezvous = jax.distributed's coordinator (PADDLE_MASTER ->
    coordinator_address); no etcd — TPU slices fail whole, so elasticity
    is restart-from-checkpoint (§5 "Failure detection"), implemented here
    as the max-restart watchdog loop.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

__all__ = ["launch", "main"]


def _parse_args(argv):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch a distributed training job")
    p.add_argument("--nnodes", type=str, default="1",
                   help="number of nodes (N or N:M elastic range)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes on this host")
    p.add_argument("--master", type=str, default=None,
                   help="coordinator endpoint host:port")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", 0)))
    p.add_argument("--devices", type=str, default=None,
                   help="visible device ids (informational on TPU)")
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--max_restart", type=int, default=3)
    p.add_argument("--heartbeat_timeout", type=float, default=0.0,
                   help="restart workers whose heartbeat file goes stale "
                        "for this many seconds (0 = disabled).  Replaces "
                        "the reference's etcd heartbeats (fleet/elastic/"
                        "manager.py — ElasticManager) with a local-file "
                        "liveness contract: workers touch "
                        "$PADDLE_HEARTBEAT_FILE via distributed.env.")
    p.add_argument("--heartbeat_startup_grace", type=float, default=0.0,
                   help="with --heartbeat_timeout set: a worker that has "
                        "written NO heartbeat after this many seconds is "
                        "treated as hung at startup (0 = 10x the "
                        "timeout).  Catches workers that wedge during "
                        "import/backend-init, BEFORE their first beat — "
                        "a plain staleness check can never see those.  "
                        "Negative disables the check (never-opted-in "
                        "workers tolerated forever).")
    p.add_argument("--elastic_devices_file", type=str, default=None,
                   help="path to a file holding the CURRENTLY available "
                        "device count; re-read on every (re)launch and "
                        "exported to workers as "
                        "PADDLE_ELASTIC_DEVICE_COUNT.  This is the TPU "
                        "recast of the reference ElasticManager's etcd "
                        "node-set watch (fleet/elastic/manager.py): the "
                        "resource set is re-evaluated at restart, workers "
                        "rebuild their mesh at the new size and resume "
                        "from the distributed checkpoint (reshard-on-load "
                        "moves the shards onto the new mesh).")
    p.add_argument("--run_mode", type=str, default="collective")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if 0 < args.heartbeat_startup_grace <= args.heartbeat_timeout:
        p.error("--heartbeat_startup_grace must exceed "
                "--heartbeat_timeout (the staleness pre-check already "
                "covers the first timeout window)")
    return args


class Container:
    """One worker subprocess (reference: launch/job/container.py)."""

    def __init__(self, rank: int, cmd: List[str], env: dict, log_path: str):
        self.rank = rank
        self.cmd = cmd
        self.env = env
        self.log_path = log_path
        self.proc: Optional[subprocess.Popen] = None
        self._log_f = None

    def start(self):
        os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
        hb = self.env.get("PADDLE_HEARTBEAT_FILE")
        if hb and os.path.exists(hb):
            os.remove(hb)          # a stale mtime from a previous attempt
        self.started_at = time.time()
        self._log_f = open(self.log_path, "ab")
        self.proc = subprocess.Popen(self.cmd, env=self.env,
                                     stdout=self._log_f,
                                     stderr=subprocess.STDOUT)

    def poll(self) -> Optional[int]:
        return self.proc.poll() if self.proc else None

    def terminate(self, grace: float = 5.0):
        if self.proc is None or self.proc.poll() is not None:
            return
        self.proc.terminate()
        try:
            self.proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        if self._log_f:
            self._log_f.close()
            self._log_f = None


class CollectiveController:
    """Builds the env contract and babysits workers (reference:
    launch/controllers/collective.py + controller.py watch loop)."""

    def __init__(self, args):
        self.args = args
        self.containers: List[Container] = []
        self.restarts = 0
        # per-job RPC auth token: workers HMAC-handshake before the rpc
        # layer unpickles anything (advisor r2: the listener executes
        # pickled callables — gate it on a launcher-scoped secret)
        import secrets
        self.rpc_token = os.environ.get("PADDLE_RPC_TOKEN") or \
            secrets.token_hex(16)

    def _endpoints(self) -> List[str]:
        base_port = int(os.environ.get("PADDLE_PORT", 61000))
        host = os.environ.get("PADDLE_LOCAL_HOST", "127.0.0.1")
        return [f"{host}:{base_port + i}"
                for i in range(self.args.nproc_per_node)]

    def build_job(self):
        args = self.args
        eps = self._endpoints()
        nnodes = int(str(args.nnodes).split(":")[0])
        world = nnodes * args.nproc_per_node
        self.containers = []
        for local_rank in range(args.nproc_per_node):
            rank = args.node_rank * args.nproc_per_node + local_rank
            env = dict(os.environ)
            env.update({
                # the reference env contract, verbatim keys
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "PADDLE_TRAINER_ENDPOINTS": ",".join(eps),
                "PADDLE_CURRENT_ENDPOINT": eps[local_rank],
                "PADDLE_LOCAL_RANK": str(local_rank),
                "PADDLE_NNODES": str(nnodes),
                "PADDLE_RESTART_COUNT": str(self.restarts),
                "PADDLE_RPC_TOKEN": self.rpc_token,
            })
            if args.master:
                env["PADDLE_MASTER"] = args.master
            if args.elastic_devices_file:
                try:
                    with open(args.elastic_devices_file) as f:
                        env["PADDLE_ELASTIC_DEVICE_COUNT"] = \
                            str(int(f.read().strip()))
                except (OSError, ValueError):
                    pass  # no file yet: workers use their own default
            if args.heartbeat_timeout > 0:
                env["PADDLE_HEARTBEAT_FILE"] = os.path.join(
                    args.log_dir, f"heartbeat.{local_rank}")
            cmd = [sys.executable, "-u", args.training_script,
                   *args.training_script_args]
            log = os.path.join(args.log_dir, f"workerlog.{local_rank}")
            self.containers.append(Container(rank, cmd, env, log))

    def start(self):
        for c in self.containers:
            c.start()

    def stop(self):
        for c in self.containers:
            c.terminate()

    def _stale_worker(self) -> Optional[tuple]:
        """(index, reason) of a live worker judged hung, else None."""
        t = self.args.heartbeat_timeout
        if t <= 0:
            return None
        now = time.time()
        for i, c in enumerate(self.containers):
            hb = c.env.get("PADDLE_HEARTBEAT_FILE")
            if not hb or c.poll() is not None:
                continue
            start_age = now - getattr(c, "started_at", now)
            if start_age < t:
                continue  # first beat may not be due yet
            try:
                age = now - os.path.getmtime(hb)
            except OSError:
                # no beat ever written: hung at startup vs not-opted-in
                # is undecidable from staleness alone — give a startup
                # grace, then treat as hung (the import/backend-init
                # wedge is precisely the failure that never beats).
                # grace < 0 disables this check (workers that never opt
                # in are tolerated forever, the pre-round-3 behavior).
                grace = self.args.heartbeat_startup_grace
                if grace < 0:
                    continue
                grace = grace or 10 * t
                if start_age > grace:
                    return i, (f"no heartbeat ever written within the "
                               f"{grace:.1f}s startup grace")
                continue
            if age > t:
                return i, f"heartbeat stale (> {t}s)"
        return None

    def watch(self) -> int:
        """Poll until all exit 0, or a failure/stale-heartbeat triggers
        teardown (+elastic restart up to --max_restart).  Returns final
        exit code."""
        while True:
            states = [c.poll() for c in self.containers]
            hung = self._stale_worker()
            if hung is not None:
                stale, why = hung
                print(f"[launch] worker {stale} heartbeat stale: {why}; "
                      f"treating as hung", file=sys.stderr)
                self.containers[stale].terminate()
                states = [c.poll() for c in self.containers]
                states[stale] = states[stale] or 1
            if any(s not in (None, 0) for s in states):
                bad = next(i for i, s in enumerate(states)
                           if s not in (None, 0))
                code = states[bad]
                self.stop()
                if self.restarts < self.args.max_restart:
                    self.restarts += 1
                    print(f"[launch] worker {bad} exited {code}; restart "
                          f"{self.restarts}/{self.args.max_restart}",
                          file=sys.stderr)
                    self.build_job()
                    self.start()
                    continue
                print(f"[launch] worker {bad} exited {code}; giving up",
                      file=sys.stderr)
                return int(code)
            if all(s == 0 for s in states):
                return 0
            time.sleep(0.2)


def launch(argv=None) -> int:
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    ctl = CollectiveController(args)
    ctl.build_job()
    ctl.start()

    def handler(signum, frame):
        ctl.stop()
        sys.exit(128 + signum)

    signal.signal(signal.SIGTERM, handler)
    signal.signal(signal.SIGINT, handler)
    return ctl.watch()


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
