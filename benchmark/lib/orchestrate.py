"""Submitting a job through client -> AM -> executor and reading back what
its containers wrote. Copied from `chip_smoke.py` (the original stays with
the program; PERF.md lists it): the system under test is the orchestrator
and what it launches, so every cell enters here. This process never
imports jax: the chip belongs to the worker or the replica.
"""

from __future__ import annotations

import os
import re
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def import_program():
    """The program's client-side modules, or exit 2 where no checkout of
    the program lies beside `benchmark/`."""
    sys.path.insert(0, ROOT)
    try:
        from tony_tpu import constants as C
        from tony_tpu.client.tony_client import TonyClient
        from tony_tpu.conf import TonyConfiguration, keys as K
        from tony_tpu.rpc.client import ClusterServiceClient
    except ImportError as e:
        print(f"benchmark: no tony_tpu checkout beside benchmark/ ({e})",
              file=sys.stderr)
        sys.exit(2)
    return C, TonyClient, TonyConfiguration, K, ClusterServiceClient


def require_no_jax() -> None:
    if "jax" in sys.modules:
        raise RuntimeError("the benchmark's own process must never import "
                           "jax: a parent that touched it holds the chip")


class Job:
    """One submitted application and its containers' logs."""

    def __init__(self, workdir: str, argv: list, timeout_s: int):
        C, TonyClient, TonyConfiguration, K, _ = import_program()
        self.C = C
        conf = TonyConfiguration()
        conf.set(K.CLUSTER_WORKDIR, workdir, "benchmark")
        conf.set(K.APPLICATION_TIMEOUT, timeout_s * 1000, "benchmark")
        self.client = TonyClient(conf)
        self.client.init(argv)
        self._monitor = None
        self.submitted_at = None

    # -- lifecycle ------------------------------------------------------
    def submit(self) -> None:
        require_no_jax()
        self.submitted_at = time.monotonic()
        self.client.submit()
        self._monitor = threading.Thread(target=self.client.monitor,
                                         daemon=True)
        self._monitor.start()

    def wait(self, timeout_s: float) -> str | None:
        """Wait for the job to end; its final status, or None in time-out."""
        self._monitor.join(timeout=timeout_s)
        if self._monitor.is_alive():
            return None
        return self.client.final_status

    def stop(self) -> None:
        self.client.cleanup()
        if self._monitor is not None:
            self._monitor.join(timeout=60)

    def kill(self) -> None:
        self.client.kill()

    @property
    def am_alive(self) -> bool:
        proc = getattr(self.client, "_am_proc", None)
        return proc is not None and proc.poll() is None

    # -- logs -----------------------------------------------------------
    def container_logs(self, which=("stdout", "stderr")) -> str:
        out = []
        if not self.client.app_dir:
            return ""
        root = os.path.join(self.client.app_dir, self.C.CONTAINERS_DIR_NAME)
        for d, _, files in sorted(os.walk(root)):
            for f in sorted(files):
                if f in which:
                    with open(os.path.join(d, f), encoding="utf-8",
                              errors="replace") as fh:
                        out.append(fh.read())
        return "\n".join(out)

    def keep_logs(self, dst: str) -> None:
        os.makedirs(dst, exist_ok=True)
        if not self.client.app_dir:
            return
        for name in (self.C.AM_STDOUT, self.C.AM_STDERR):
            src = os.path.join(self.client.app_dir, name)
            if os.path.exists(src):
                shutil.copy(src, os.path.join(dst, name))
        root = os.path.join(self.client.app_dir, self.C.CONTAINERS_DIR_NAME)
        for d, _, files in os.walk(root):
            for f in files:
                if f in ("stdout", "stderr"):
                    rel = os.path.relpath(d, root).replace(os.sep, "_")
                    shutil.copy(os.path.join(d, f),
                                os.path.join(dst, f"{rel}.{f}"))

    # -- serving --------------------------------------------------------
    def wait_endpoint(self, deadline: float) -> str:
        """The endpoint the replica registered with the AM."""
        C = self.C
        _, _, _, _, ClusterServiceClient = import_program()
        hostport = os.path.join(self.client.app_dir, C.AM_HOSTPORT_FILE)
        while time.monotonic() < deadline and not os.path.exists(hostport):
            time.sleep(0.1)
        if not os.path.exists(hostport):
            raise RuntimeError("AM never came up (no amhostport file)")
        with open(hostport, encoding="utf-8") as f:
            host, _, port = f.read().strip().rpartition(":")
        rpc = ClusterServiceClient(host, int(port), retries=2,
                                   retry_sleep_sec=0.2, timeout_sec=5.0,
                                   auth_token=self.client.auth_token)
        try:
            while time.monotonic() < deadline:
                if not self.am_alive:
                    raise RuntimeError("AM exited before an endpoint "
                                       "registered")
                try:
                    infos = rpc.get_task_infos()
                except (OSError, RuntimeError, ValueError):   # AM mid-boot
                    infos = []
                for info in infos:
                    if info.get("name") == "serving-endpoint":
                        return info["url"]
                time.sleep(0.25)
        finally:
            rpc.close()
        raise RuntimeError("serving endpoint never registered")


DEVICE_LINE = re.compile(r"devices: (\d+) x (.+?) \(backend=(\w+)\)")


def device_of(logs: str):
    """(count, kind, platform) from the device line the worker and the
    replica both log (train/metrics.py log_devices)."""
    m = DEVICE_LINE.search(logs)
    return (int(m.group(1)), m.group(2), m.group(3)) if m else None


def marked_json(logs: str, mark: str) -> list:
    """Every JSON object a launcher printed on a line starting `mark `."""
    import json
    out = []
    for line in logs.splitlines():
        if line.startswith(mark + " "):
            try:
                out.append(json.loads(line[len(mark) + 1:]))
            except ValueError:
                pass
    return out
