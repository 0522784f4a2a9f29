"""Multi-process launcher — `python -m paddle_tpu.distributed.launch`.

Reference: python/paddle/distributed/fleet/launch.py:208
(launch_collective), launch_utils.py:164 (Pod), :258 (get_cluster),
:435-491 (start_local_trainers: one subprocess per device with
PADDLE_TRAINER_ID/PADDLE_TRAINER_ENDPOINTS env + log redirection),
:526 (watch_local_trainers: tear the pod down when any trainer dies).

TPU-native deltas: the rendezvous is JAX's coordinator service
(jax.distributed.initialize inside env.init_parallel_env) instead of a
raw-TCP ncclUniqueId exchange, so the launcher only has to agree on a
coordinator address and export the same PADDLE_* env contract the
reference uses. On a TPU pod slice the runtime usually launches one
process per host out-of-band; this launcher covers single-host
multi-process (CPU rings, tests — the reference's localhost cluster
strategy, test_dist_base.py:668) and explicit multi-host via --ips.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import List, Optional

__all__ = ["launch", "get_cluster", "Pod", "TrainerProc", "find_free_port",
           "read_hosts_file", "HOSTS_FILE_ENV"]

# elastic membership: a file the scheduler/operator keeps current with
# the SURVIVING host set (one `ip[:nproc]` per line, '#' comments).
# When set, every (re)launch attempt re-reads it, so a pod that lost a
# host after preemption re-forms over the survivors at a smaller world
# size instead of demanding the original --ips back; the trainers then
# elastic-restore their checkpoints onto the smaller mesh.
HOSTS_FILE_ENV = "PADDLE_ELASTIC_HOSTS_FILE"


def read_hosts_file(path: Optional[str],
                    default_nproc: int) -> Optional[list]:
    """[(ip, nproc)] from an elastic hosts file.  None means 'no
    membership info' (missing/unreadable file -> caller falls back to
    the static --ips contract); an EMPTY list is meaningful — the
    operator truncated the file to say zero hosts survive, and the
    launcher must give up rather than relaunch at the old world size."""
    if not path or not os.path.isfile(path):
        return None
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if not line:
                    continue
                ip, _, n = line.partition(":")
                try:
                    nproc = int(n) if n else default_nproc
                except ValueError:
                    nproc = default_nproc
                out.append((ip.strip(), max(1, nproc)))
    except OSError:
        return None
    return out


def find_free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class TrainerProc:
    """reference launch_utils.py TrainerProc."""
    rank: int
    proc: subprocess.Popen
    log_path: Optional[str] = None
    log_fh: object = None


@dataclass
class Pod:
    """This host's slice of the cluster (reference launch_utils.py:164)."""
    addr: str
    ranks: List[int] = field(default_factory=list)
    endpoints: List[str] = field(default_factory=list)


def get_cluster(ips: List[str], nproc_per_node: int,
                start_port: Optional[int] = None,
                nproc_map: Optional[dict] = None):
    """All endpoints + this host's Pod (reference get_cluster:258).
    nproc_map ({ip: nproc}) lets an elastic relaunch give survivors
    per-host process counts that differ from the static default."""
    endpoints, pods = [], []
    for ip in ips:
        nproc = (nproc_map or {}).get(ip, nproc_per_node)
        ports = [find_free_port() if (start_port is None and
                                      ip in ("127.0.0.1", "localhost"))
                 else (start_port or 6170) + i
                 for i in range(nproc)]
        pod = Pod(addr=ip)
        for p in ports:
            pod.ranks.append(len(endpoints))
            ep = f"{ip}:{p}"
            pod.endpoints.append(ep)
            endpoints.append(ep)
        pods.append(pod)
    return endpoints, pods


def trainer_env_vars(rank: int, world: int, endpoints: List[str],
                     coordinator: str) -> dict:
    """The per-rank env contract — single source of truth shared with
    spawn.py (reference launch_utils.py:435-466)."""
    return {
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        # TPU-native rendezvous (env.init_parallel_env)
        "PADDLE_MASTER": coordinator,
        "JAX_COORDINATOR_ADDRESS": coordinator,
    }


def _trainer_env(rank: int, world: int, endpoints: List[str],
                 coordinator: str) -> dict:
    env = dict(os.environ)
    env.update(trainer_env_vars(rank, world, endpoints, coordinator))
    return env


def _local_addrs(probe_ips=()) -> set:
    addrs = {"127.0.0.1", "localhost"}
    try:
        host = socket.gethostname()
        addrs.add(host)
        addrs.add(socket.gethostbyname(host))
    except OSError:  # pragma: no cover
        pass
    # hostname often resolves to 127.0.1.1, not the NIC address in --ips;
    # the UDP-connect trick reveals the interface used to reach each peer
    for ip in probe_ips:
        try:
            with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                s.connect((ip, 9))
                addrs.add(s.getsockname()[0])
        except OSError:  # pragma: no cover
            pass
    return addrs


def start_local_trainers(pod: Pod, world: int, endpoints: List[str],
                         coordinator: str, training_script: str,
                         script_args: List[str],
                         log_dir: Optional[str] = None
                         ) -> List[TrainerProc]:
    """reference start_local_trainers (launch_utils.py:435)."""
    procs = []
    for rank in pod.ranks:
        env = _trainer_env(rank, world, endpoints, coordinator)
        cmd = [sys.executable, "-u", training_script] + list(script_args)
        log_fh, log_path = None, None
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            log_path = os.path.join(log_dir, f"workerlog.{rank}")
            log_fh = open(log_path, "w")
        proc = subprocess.Popen(
            cmd, env=env,
            stdout=log_fh if log_fh else None,
            stderr=subprocess.STDOUT if log_fh else None)
        procs.append(TrainerProc(rank=rank, proc=proc, log_path=log_path,
                                 log_fh=log_fh))
    return procs


HEARTBEAT_ENV = "PADDLE_HEARTBEAT_DIR"
RC_HEARTBEAT_LOST = 98  # pod exit code for a hung (not crashed) trainer


def heartbeat_path(hb_dir: str, rank: int) -> str:
    return os.path.join(hb_dir, f"hb.{rank}")


def watch_local_trainers(procs: List[TrainerProc],
                         poll_interval: float = 0.5,
                         heartbeat_dir: Optional[str] = None,
                         heartbeat_timeout: float = 0.0) -> int:
    """Tear the pod down when any trainer dies (reference
    watch_local_trainers, launch_utils.py:526) — or, with heartbeats
    enabled, when any trainer goes silent for heartbeat_timeout seconds
    (the failure-detection role of the reference's elastic manager; a
    rank hung in a dead collective never exits on its own).  Returns the
    pod's exit code (first non-zero child, RC_HEARTBEAT_LOST for hangs,
    else 0)."""
    start = time.time()
    try:
        while True:
            alive, rc = 0, 0
            for t in procs:
                code = t.proc.poll()
                if code is None:
                    alive += 1
                elif code != 0:
                    rc = code
            if rc != 0:
                _terminate(procs)
                return rc
            if alive == 0:
                return 0
            if heartbeat_dir and heartbeat_timeout > 0:
                now = time.time()
                for t in procs:
                    if t.proc.poll() is not None:
                        continue
                    p = heartbeat_path(heartbeat_dir, t.rank)
                    try:
                        last = os.path.getmtime(p)
                    except OSError:
                        # no beat yet: measure from launch (startup +
                        # first compile count against the same budget)
                        last = start
                    if now - last > heartbeat_timeout:
                        print(f"launch: rank {t.rank} heartbeat lost "
                              f"({now - last:.0f}s > "
                              f"{heartbeat_timeout:.0f}s); tearing down",
                              file=sys.stderr, flush=True)
                        _terminate(procs)
                        return RC_HEARTBEAT_LOST
            time.sleep(poll_interval)
    except KeyboardInterrupt:  # pragma: no cover
        _terminate(procs)
        raise
    finally:
        for t in procs:
            if t.log_fh:
                t.log_fh.close()


def _terminate(procs: List[TrainerProc], grace: float = 3.0):
    for t in procs:
        if t.proc.poll() is None:
            t.proc.terminate()
    deadline = time.time() + grace
    for t in procs:
        while t.proc.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        if t.proc.poll() is None:
            t.proc.kill()


def launch(args=None) -> int:
    parser = argparse.ArgumentParser(
        "paddle_tpu.distributed.launch",
        description="start one training process per rank "
                    "(reference fleet/launch.py)")
    parser.add_argument("--nproc_per_node", type=int, default=1)
    parser.add_argument("--ips", type=str, default="127.0.0.1",
                        help="comma-separated host ips")
    parser.add_argument("--log_dir", type=str, default=None)
    parser.add_argument("--start_port", type=int, default=None)
    parser.add_argument("--elastic_retries", type=int, default=0,
                        help="relaunch the whole pod up to N times after "
                             "a crash or lost heartbeat (pair with "
                             "checkpoint auto-resume for fault-tolerant "
                             "training)")
    parser.add_argument("--heartbeat_timeout", type=float, default=0.0,
                        help="seconds of trainer silence before the pod "
                             "is declared hung (0 = disabled); trainers "
                             "beat automatically from train_step")
    parser.add_argument("--elastic_hosts_file", type=str,
                        default=os.environ.get(HOSTS_FILE_ENV),
                        help="membership file re-read before every "
                             "(re)launch attempt: one `ip[:nproc]` per "
                             "line — the SURVIVING host set. With it, a "
                             "preemption drain or crash relaunches over "
                             "whatever hosts remain (smaller world size) "
                             "and the trainers elastic-restore their "
                             "checkpoints onto the new mesh, instead of "
                             "requiring the original --ips world back")
    parser.add_argument("training_script", type=str)
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    a = parser.parse_args(args)

    static_ips = [ip.strip() for ip in a.ips.split(",") if ip.strip()]

    def _resolve_hosts():
        """Current host set: the elastic hosts file when given (re-read
        per attempt — it IS the surviving set), else the static --ips."""
        hosts = read_hosts_file(a.elastic_hosts_file, a.nproc_per_node)
        if hosts is None:
            return static_ips, None
        return [ip for ip, _ in hosts], {ip: n for ip, n in hosts}

    # preemption handling: SIGTERM on the launcher forwards to every
    # trainer so their PreemptionGuards drain the in-flight step and
    # checkpoint; the pod then exits with the trainers' status instead
    # of elastic-restarting into a doomed relaunch
    current_procs: List[TrainerProc] = []
    preempted = [False]

    def _forward_sigterm(signum, frame):
        preempted[0] = True
        print("launch: SIGTERM received; forwarding to trainers for "
              "drain + checkpoint", file=sys.stderr, flush=True)
        for t in current_procs:
            if t.proc.poll() is None:
                t.proc.terminate()

    try:
        prev_term = signal.signal(signal.SIGTERM, _forward_sigterm)
    except ValueError:  # pragma: no cover (non-main thread)
        prev_term = None

    attempts = a.elastic_retries + 1
    for attempt in range(attempts):
        # fresh ports each attempt: the dead pod's sockets may linger;
        # fresh membership each attempt: survivors only (elastic shrink)
        ips, nproc_map = _resolve_hosts()
        if not ips:
            print("launch: elastic hosts file lists no survivors; "
                  "giving up", file=sys.stderr, flush=True)
            return 1
        endpoints, pods = get_cluster(ips, a.nproc_per_node,
                                      a.start_port, nproc_map)
        # pick THIS host's pod (reference matches the node ip); each host
        # of a multi-host cluster runs its own launcher over the same
        # --ips
        if len(pods) == 1:
            pod = pods[0]
        else:
            local = _local_addrs(probe_ips=ips)
            mine = [p for p in pods if p.addr in local]
            if not mine:
                raise SystemExit(
                    f"none of --ips {ips} matches this host "
                    f"({sorted(local)}); include this host's ip")
            pod = mine[0]
        coordinator = f"{ips[0]}:{find_free_port()}" if ips[0] in (
            "127.0.0.1", "localhost") else endpoints[0]

        hb_dir = None
        if a.heartbeat_timeout > 0:
            hb_dir = a.log_dir or os.path.join(
                os.environ.get("TMPDIR", "/tmp"),
                f"paddle_hb_{os.getpid()}_{attempt}")
            os.makedirs(hb_dir, exist_ok=True)
            # stale beats from a previous attempt/run would trip the
            # watchdog instantly — each attempt starts with a clean slate
            for f in os.listdir(hb_dir):
                if f.startswith("hb."):
                    try:
                        os.remove(os.path.join(hb_dir, f))
                    except OSError:
                        pass
            os.environ[HEARTBEAT_ENV] = hb_dir  # inherited by children

        procs = start_local_trainers(pod, len(endpoints), endpoints,
                                     coordinator, a.training_script,
                                     a.script_args, a.log_dir)
        current_procs[:] = procs
        rc = watch_local_trainers(procs,
                                  heartbeat_dir=hb_dir,
                                  heartbeat_timeout=a.heartbeat_timeout)
        if preempted[0] and a.elastic_hosts_file and \
                attempt + 1 < attempts:
            # SIGTERM drain finished (trainers checkpointed + exited):
            # instead of dying at the original world size, re-form the
            # mesh from whatever the hosts file NOW lists — the
            # surviving set — and let auto-resume elastic-restore the
            # checkpoints onto the smaller (or regrown) topology
            preempted[0] = False
            print("launch: preemption drain complete; re-forming from "
                  "the surviving host set", file=sys.stderr, flush=True)
            time.sleep(0.5)
            continue
        if rc == 0 or preempted[0]:
            # clean finish, or a preemption drain (trainers that
            # checkpointed and exited 0 make the whole pod exit 0)
            if prev_term is not None:
                signal.signal(signal.SIGTERM, prev_term)
            return rc
        if attempt + 1 < attempts:
            print(f"launch: pod failed (rc={rc}); elastic restart "
                  f"{attempt + 2}/{attempts}", file=sys.stderr,
                  flush=True)
            time.sleep(1.0)
    if prev_term is not None:
        signal.signal(signal.SIGTERM, prev_term)
    return rc


if __name__ == "__main__":
    sys.exit(launch())
