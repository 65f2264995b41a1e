#!/usr/bin/env python
"""Flight-recorder-instrumented repro drive for the PR-7 shm flake.

The flake: under post-failover same-host churn (workers and the primary
PS being ``kill -9``-ed while the shm fused data plane is active), the
BACKUP PS rarely (~1/6 observed) died with SIGSEGV; ``PSDT_SHM=0`` was
stable on the same drive.  Suspected cause: a double segment reap — the
serve-thread exit reap racing the shutdown/negotiation-failure unlink,
the second unmap pulling the mapping out from under a native ring copy.

This script is the scripted kill-9 churn drive, with every process
running under ``PSDT_FLIGHT_DIR`` so a crash leaves decodable rings —
including the dead process's own.  It:

1. launches coordinator + primary PS (sync-replicating) + backup PS +
   2 workers as real processes with ``PSDT_SHM=1``;
2. churns: repeatedly ``kill -9``-s a worker mid-run and restarts it,
   and once mid-drive kills the PRIMARY so the backup is promoted and
   the churn continues against the promoted replica — the post-failover
   same-host pattern the flake needed;
3. watches the backup: if it dies, the flake reproduced — the script
   runs ``pst-trace`` over the flight directory and prints the decoded
   evidence (the dead backup's ring ends with the double ``shm.reap``
   and the open native copy; see docs/observability.md).

Usage:
    python scripts/shm_churn_repro.py [--rounds=N] [--dir=FLIGHT_DIR]
                                      [--no-shm]

Exit status: 0 = drive completed with the backup alive (post-fix
expectation; the ``shm.reap.dup`` events in the rings show the latch
absorbing the double-reap attempts), 3 = backup died (pre-fix flake
reproduced; evidence printed).
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "parameter_server_distributed_tpu"


def _spawn(args: list[str], env: dict, log_path: str) -> subprocess.Popen:
    log_fh = open(log_path, "ab")
    return subprocess.Popen([sys.executable, "-m", *args], env=env,
                            cwd=REPO, stdout=log_fh, stderr=log_fh)


def main(argv: list[str] | None = None) -> int:
    sys.path.insert(0, REPO)
    from parameter_server_distributed_tpu.config import parse_argv

    _, flags = parse_argv(sys.argv[1:] if argv is None else argv)
    rounds = int(flags.get("rounds", 6))
    flight_dir = flags.get("dir") or tempfile.mkdtemp(prefix="psdt-flight-")
    use_shm = "no-shm" not in flags

    base = 21300 + (os.getpid() % 500) * 10
    coord_addr = f"127.0.0.1:{base}"
    primary_addr = f"127.0.0.1:{base + 1}"
    backup_addr = f"127.0.0.1:{base + 2}"

    env = dict(os.environ)
    env.update({
        "PSDT_FLIGHT_DIR": flight_dir,
        "PSDT_SHM": "1" if use_shm else "0",
        "JAX_PLATFORMS": "cpu",
        "JAX_PLATFORMS": "cpu",
    })
    logs = os.path.join(flight_dir, "logs")
    os.makedirs(logs, exist_ok=True)
    print(f"churn drive: flight dir {flight_dir} (shm "
          f"{'on' if use_shm else 'off'}), {rounds} kill rounds")

    procs: dict[str, subprocess.Popen] = {}

    def start_worker(wid: int) -> None:
        procs[f"worker{wid}"] = _spawn(
            [f"{PKG}.cli.worker_main", coord_addr, str(wid), "500",
             "127.0.0.1", str(base + 5 + wid), "", "--model=mnist_mlp",
             "--batch=16"],
            env, os.path.join(logs, f"worker{wid}.log"))

    try:
        procs["backup"] = _spawn(
            [f"{PKG}.cli.ps_main", backup_addr, "2", "1000000",
             f"--ckpt-dir={os.path.join(flight_dir, 'ck-b')}"],
            env, os.path.join(logs, "backup.log"))
        procs["primary"] = _spawn(
            [f"{PKG}.cli.ps_main", primary_addr, "2", "1000000",
             f"--backup={backup_addr}", "--replication=sync",
             f"--ckpt-dir={os.path.join(flight_dir, 'ck-p')}"],
            env, os.path.join(logs, "primary.log"))
        procs["coordinator"] = _spawn(
            [f"{PKG}.cli.coordinator_main", coord_addr, primary_addr,
             f"--ps-backups={backup_addr}"],
            env, os.path.join(logs, "coordinator.log"))
        time.sleep(3.0)
        start_worker(0)
        start_worker(1)
        time.sleep(5.0)  # let fused+shm rounds establish

        killed_primary = False
        for r in range(rounds):
            victim = f"worker{r % 2}"
            proc = procs.get(victim)
            if proc is not None and proc.poll() is None:
                print(f"round {r}: kill -9 {victim}")
                proc.send_signal(signal.SIGKILL)
                proc.wait()
            time.sleep(1.0)
            start_worker(r % 2)
            if not killed_primary and r >= rounds // 2:
                # mid-drive failover: kill the primary; the workers
                # report it and the backup is promoted — churn continues
                # against the promoted replica (the flake's habitat)
                print(f"round {r}: kill -9 PRIMARY (forcing promotion)")
                procs["primary"].send_signal(signal.SIGKILL)
                procs["primary"].wait()
                killed_primary = True
            time.sleep(2.0)
            backup = procs["backup"]
            if backup.poll() is not None:
                rc = backup.returncode
                print(f"BACKUP DIED (rc={rc}, signal "
                      f"{-rc if rc and rc < 0 else 'n/a'}) — flake "
                      f"reproduced")
                status = 3
                break
        else:
            print("drive complete: backup alive across churn + failover")
            status = 0
    finally:
        # kill everything BEFORE decoding: the postmortem's liveness
        # probe would otherwise (correctly) list the survivors as
        # "still running" instead of closing out the drive's story
        for proc in procs.values():
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
        for proc in procs.values():
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        print(f"flight rings preserved under {flight_dir}")
    print("decoding flight evidence:")
    _postmortem(flight_dir)
    return status


def _postmortem(flight_dir: str) -> None:
    from parameter_server_distributed_tpu.cli.trace_main import main as trace
    trace([flight_dir])


if __name__ == "__main__":
    sys.exit(main())
