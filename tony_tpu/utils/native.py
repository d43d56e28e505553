"""On-demand build + launch of the native helpers in src/native/.

The reference shipped its helpers inside a fat jar; here the C++ helpers
(epoll TCP proxy, SO_REUSEPORT port reservation — SURVEY.md §7 "native
equivalents") are compiled lazily with the system toolchain and cached in
src/native/build/. Every caller has a pure-Python fallback, so a missing
compiler degrades gracefully instead of failing the job.
"""

from __future__ import annotations

import hashlib
import logging
import os
import platform
import shutil
import subprocess
import threading
from typing import Optional

LOG = logging.getLogger(__name__)

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "src", "native")

_build_lock = threading.Lock()
_build_failed = False
_build_checked = False
_STAMP = os.path.join(NATIVE_DIR, "build", ".stamp")


def _source_stamp() -> str:
    """What a build in src/native/build/ must have been made from to be
    used: the committed sources and Makefile, byte for byte, on this
    machine's architecture and C library. The build directory is not
    committed, but it travels with a copied tree — a binary built from
    older sources, or on another machine, must not be preferred over the
    sources that are here."""
    h = hashlib.sha256()
    for name in sorted(os.listdir(NATIVE_DIR)):
        if name.endswith(".cc") or name == "Makefile":
            h.update(name.encode())
            with open(os.path.join(NATIVE_DIR, name), "rb") as f:
                h.update(f.read())
    h.update(repr((platform.machine(), platform.libc_ver())).encode())
    return h.hexdigest()


def native_binary(name: str) -> Optional[str]:
    """Absolute path of a built native helper, building all helpers on
    first use; None if the toolchain is unavailable or the build fails."""
    global _build_failed, _build_checked
    path = os.path.join(NATIVE_DIR, "build", name)
    with _build_lock:
        if _build_failed:
            return None
        if _build_checked:
            return path if os.path.isfile(path) else None
        stamp = _source_stamp()
        try:
            with open(_STAMP, encoding="utf-8") as f:
                fresh = f.read().strip() == stamp
        except OSError:
            fresh = False
        if fresh and os.path.isfile(path) and os.access(path, os.X_OK):
            _build_checked = True
            return path
        if shutil.which("make") is None or shutil.which("g++") is None:
            LOG.info("no native toolchain; using pure-Python fallbacks")
            _build_failed = True
            return None
        try:
            # serializing the one-time native build IS this lock's
            # purpose; no control-plane path shares it. Built into a
            # private directory and renamed into place, so a concurrent
            # process never runs a half-written binary
            tmp = f"build.{os.getpid()}"
            # tony: disable=no-blocking-under-lock -- build lock, not control plane
            subprocess.run(["make", "-s", "-B",
                            f"BUILD={tmp}"], cwd=NATIVE_DIR, check=True,
                           capture_output=True, timeout=120)
            with open(os.path.join(NATIVE_DIR, tmp, ".stamp"), "w",
                      encoding="utf-8") as f:
                f.write(stamp + "\n")
            build = os.path.join(NATIVE_DIR, "build")
            os.makedirs(build, exist_ok=True)
            for built in sorted(os.listdir(os.path.join(NATIVE_DIR, tmp)),
                                key=lambda n: n == ".stamp"):
                os.replace(os.path.join(NATIVE_DIR, tmp, built),
                           os.path.join(build, built))   # stamp last
            os.rmdir(os.path.join(NATIVE_DIR, tmp))
        except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
                OSError) as e:
            out = getattr(e, "stderr", b"") or str(e).encode()
            LOG.warning("native build failed, using Python fallbacks: %s",
                        out.decode(errors="replace")[-500:])
            _build_failed = True
            return None
        _build_checked = True
    return path if os.path.isfile(path) else None


def launch_native_proxy(remote_host: str, remote_port: int,
                        local_port: int = 0, token: str = ""):
    """Start the native proxy; returns (Popen, bound_local_port) or None if
    native is unavailable. Caller owns the process. `token` (passed via
    env, never argv) makes the relay require connection auth — see
    tony_tpu/proxy.py module docstring for the protocol."""
    binary = native_binary("tony_proxy")
    if binary is None:
        return None
    argv = [binary, remote_host, str(remote_port)]
    if local_port:
        argv.append(str(local_port))
    env = dict(os.environ)
    if token:
        env["TONY_PROXY_TOKEN"] = token
    else:
        env.pop("TONY_PROXY_TOKEN", None)
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE, text=True, env=env)
    line = proc.stdout.readline()  # "proxying 127.0.0.1:<port> -> ..."
    try:
        bound = int(line.split("->")[0].strip().rsplit(":", 1)[1])
    except (IndexError, ValueError):
        proc.kill()
        LOG.warning("unexpected native proxy banner %r; falling back", line)
        return None
    return proc, bound


def launch_port_reservation(sentinel: str, n_ports: int = 1):
    """Hold n ports with SO_REUSEPORT from the native helper process
    (reference: ReusablePort.java:149-235 spawning its python helper).
    Returns (Popen, [ports]) or None if native is unavailable."""
    binary = native_binary("tony_portres")
    if binary is None:
        return None
    proc = subprocess.Popen([binary, sentinel, str(n_ports)],
                            stdout=subprocess.PIPE, text=True)
    ports = []
    for _ in range(n_ports):
        line = proc.stdout.readline().strip()
        if not line.isdigit():
            proc.kill()
            LOG.warning("unexpected portres output %r; falling back", line)
            return None
        ports.append(int(line))
    # wait for the readiness sentinel (bounded)
    import time
    deadline = time.monotonic() + 10
    while not os.path.exists(sentinel):
        if time.monotonic() > deadline or proc.poll() is not None:
            proc.kill()
            return None
        time.sleep(0.01)
    return proc, ports
