"""Node bootstrap: start/stop the head-node process tree.

Reference analog: python/ray/_private/node.py (:1117-1429) and services.py
(start_gcs_server:1445, start_raylet:1529): the driver spawns the GCS and a
raylet as subprocesses and connects to them.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import uuid
from typing import Dict, List, Optional, Tuple


class NodeProcesses:
    def __init__(self, session_dir: str):
        self.session_dir = session_dir
        self.gcs_proc: Optional[subprocess.Popen] = None
        self.raylet_proc: Optional[subprocess.Popen] = None
        self.dashboard_proc: Optional[subprocess.Popen] = None
        self.dashboard_url: Optional[str] = None
        self.gcs_address: Optional[Tuple[str, int]] = None
        self.raylet_address: Optional[Tuple[str, int]] = None
        self.node_id: Optional[bytes] = None
        self.store_path: Optional[str] = None


def new_session_dir() -> str:
    base = os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu")
    session = os.path.join(base, f"session_{int(time.time())}_{uuid.uuid4().hex[:8]}")
    os.makedirs(os.path.join(session, "logs"), exist_ok=True)
    # session_latest lets same-host attachers (CLI status/join, driver
    # init(address=...)) find the auth token without an env var (reference
    # analog: /tmp/ray/session_latest).
    latest = os.path.join(base, "session_latest")
    tmp = f"{latest}.{os.getpid()}.tmp"
    try:
        os.symlink(session, tmp)
        os.replace(tmp, latest)
    except OSError:
        pass
    return session


def _spawn(session_dir: str, name: str, cmd: List[str], **popen_kw) -> subprocess.Popen:
    """Start one of a session's processes and write it into the session's
    record, `<session>/pids` (a line `name pid` a process). Every process a
    session starts on its own account comes through here, so what belongs to
    a session is read from the session (`session_pids`), never guessed from
    the host's process table, where other sessions' processes look alike."""
    proc = subprocess.Popen(cmd, start_new_session=True, **popen_kw)
    with open(os.path.join(session_dir, "pids"), "a") as f:
        f.write(f"{name} {proc.pid}\n")
    return proc


def session_pids(session_dir: str) -> List[Tuple[str, int]]:
    """(name, pid) of every process the session started, in order; a
    restarted GCS appears once for each start."""
    try:
        with open(os.path.join(session_dir, "pids")) as f:
            rows = [line.split() for line in f]
    except OSError:
        return []
    return [(name, int(pid)) for name, pid in rows]


def child_pids(pid: int) -> List[int]:
    """Direct children of `pid` (via /proc), best-effort: a raylet's workers."""
    out: List[int] = []
    try:
        entries = [e for e in os.listdir("/proc") if e.isdigit()]
    except OSError:
        return out
    for entry in entries:
        try:
            with open(f"/proc/{entry}/status") as f:
                for line in f:
                    if line.startswith("PPid:"):
                        if int(line.split()[1]) == pid:
                            out.append(int(entry))
                        break
        except OSError:
            continue
    return out


def _wait_file(path: str, timeout: float, proc: subprocess.Popen, what: str) -> str:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return f.read()
        if proc.poll() is not None:
            raise RuntimeError(f"{what} exited with code {proc.returncode} during startup "
                               f"(logs in {os.path.dirname(path)})")
        time.sleep(0.02)
    raise RuntimeError(f"timed out waiting for {what} to start")


def ensure_auth_token(session_dir: str) -> None:
    """Mint the per-session wire-auth token (rpc.py challenge-response).

    Every cluster process descends from the process that starts the GCS, so
    setting RAY_TPU_AUTH_TOKEN here propagates to GCS/raylet/worker/driver
    children via env inheritance; the 0600 session file lets a same-host
    operator attach out-of-band. An already-set env token is kept (attach
    to an existing cluster / explicit operator-provided token)."""
    if os.environ.get("RAY_TPU_AUTH_TOKEN"):
        token_hex = os.environ["RAY_TPU_AUTH_TOKEN"]
        try:
            bytes.fromhex(token_hex)
        except ValueError:
            raise RuntimeError(
                "RAY_TPU_AUTH_TOKEN must be a hex string; "
                f"got {len(token_hex)} chars of non-hex")
    else:
        token_hex = os.urandom(32).hex()
        os.environ["RAY_TPU_AUTH_TOKEN"] = token_hex
    path = os.path.join(session_dir, "auth_token")
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    with os.fdopen(fd, "w") as f:
        f.write(token_hex)
    from ray_tpu.runtime import rpc

    rpc.set_session_token(bytes.fromhex(token_hex))


def start_gcs(session_dir: str, port: int = 0,
              storage: Optional[str] = None
              ) -> Tuple[subprocess.Popen, Tuple[str, int]]:
    """storage defaults to <session>/gcs.db — GCS restarts recover state
    (pass storage="" to run purely in-memory)."""
    ensure_auth_token(session_dir)
    if storage is None:
        storage = os.path.join(session_dir, "gcs.db")
    ready = os.path.join(session_dir, f"gcs_ready_{os.getpid()}_{port}")
    try:
        os.unlink(ready)
    except OSError:
        pass
    log = open(os.path.join(session_dir, "logs", "gcs.log"), "ab")
    cmd = [sys.executable, "-m", "ray_tpu.runtime.gcs.main",
           "--ready-file", ready, "--port", str(port)]
    if storage:
        cmd += ["--storage", storage]
    proc = _spawn(session_dir, "gcs", cmd, stdout=log, stderr=subprocess.STDOUT)
    log.close()
    addr = _wait_file(ready, 60, proc, "GCS")
    host, port = addr.rsplit(":", 1)
    # Record the address in the session dir so same-host attachers can
    # resolve the RIGHT session's auth token by the address they attach to
    # (session_latest alone mis-resolves when two clusters share a host —
    # rpc.load_token_for_address scans these files).
    with open(os.path.join(session_dir, "gcs_address"), "w") as f:
        f.write(f"{host}:{port}")
    return proc, (host, int(port))


def start_raylet(session_dir: str, gcs_address: Tuple[str, int],
                 resources: Dict[str, float], labels: Dict[str, str],
                 object_store_memory: int, is_head: bool = False,
                 worker_env: Optional[Dict[str, str]] = None,
                 name: str = "raylet") -> Tuple[subprocess.Popen, dict]:
    ready = os.path.join(session_dir, f"{name}_ready_{uuid.uuid4().hex[:6]}")
    log = open(os.path.join(session_dir, "logs", f"{name}.log"), "ab")
    cmd = [sys.executable, "-m", "ray_tpu.runtime.raylet.main",
           "--gcs-address", f"{gcs_address[0]}:{gcs_address[1]}",
           "--session-dir", session_dir,
           "--resources", json.dumps(resources),
           "--labels", json.dumps(labels),
           "--object-store-memory", str(object_store_memory),
           "--worker-env", json.dumps(worker_env or {}),
           "--ready-file", ready]
    if is_head:
        cmd.append("--is-head")
    proc = _spawn(session_dir, name, cmd, stdout=log, stderr=subprocess.STDOUT)
    log.close()
    info = json.loads(_wait_file(ready, 60, proc, "raylet"))
    return proc, info


def stop_raylet(proc: subprocess.Popen, store_path: Optional[str], *,
                force: bool = False, timeout: float = 10.0) -> None:
    """End a raylet this session started, and leave no arena behind.

    The arena is a file in /dev/shm that outlives every process that maps
    it. A raylet that stops on its own terms unlinks it; one that is killed
    cannot, and whoever killed it is the only one who knows. So every path
    that ends a raylet from outside comes through here, and the unlink
    happens once the process is gone, whichever way it went.

    force=True is a host's death: SIGKILL, to the raylet and to its
    workers. Workers run in sessions of their own, so killing the raylet
    alone would leave them serving, which no real failure does.
    force=False is SIGTERM (the raylet reaps its workers and unlinks), and
    the host's death after `timeout` seconds without an exit."""
    if proc.poll() is None and not force:
        proc.terminate()
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            pass
    if proc.poll() is None:
        workers = child_pids(proc.pid)
        proc.kill()
        proc.wait(timeout=timeout)
        for pid in workers:
            try:
                os.killpg(pid, signal.SIGKILL)
            except OSError:
                pass
    if store_path:
        try:
            os.unlink(store_path)
        except OSError:
            pass


def start_dashboard(session_dir: str, gcs_address: Tuple[str, int],
                    host: str = "127.0.0.1", port: int = 0
                    ) -> Tuple[subprocess.Popen, str]:
    """Start the dashboard head (REST/metrics/job API) as a subprocess.

    Reference analog: _private/services.py start_dashboard -> dashboard/head.py.
    Returns (proc, url). The child prints a {"port": N} JSON line once bound.
    """
    log_path = os.path.join(session_dir, "logs", "dashboard.log")
    log = open(log_path, "ab")
    proc = _spawn(
        session_dir, "dashboard",
        [sys.executable, "-m", "ray_tpu.dashboard.head",
         "--gcs-address", f"{gcs_address[0]}:{gcs_address[1]}",
         "--session-dir", session_dir, "--host", host, "--port", str(port)],
        stdout=subprocess.PIPE, stderr=log)
    log.close()
    # Non-blocking read of the child's {"port": N} announce line: readline()
    # would ignore the deadline if the child hangs before printing.
    import select

    fd = proc.stdout.fileno()
    os.set_blocking(fd, False)
    buf = b""
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"dashboard exited with code {proc.returncode}; see {log_path}")
        if select.select([fd], [], [], 0.2)[0]:
            chunk = os.read(fd, 4096)
            if chunk:
                buf += chunk
            if b"\n" in buf:
                break
    line = buf.split(b"\n", 1)[0].strip()
    if not line:
        proc.kill()
        raise RuntimeError(
            f"dashboard did not announce its port within 30s; see {log_path}")
    bound = json.loads(line)["port"]
    return proc, f"http://{host}:{bound}"
