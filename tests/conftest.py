"""Test harness config.

Forces JAX onto a virtual 8-device CPU platform so multi-chip sharding tests
run without TPU hardware (the tony-mini / MiniYARNCluster analogue for the
compute plane — SURVEY.md §4 takeaway). Must run before the first jax import
anywhere in the test process.

The orchestrator E2E suite spawns many python processes (AM, executors, user
scripts) that inherit this env: control-plane processes must never claim an
accelerator, and test user-processes run on CPU.
"""

import os
import sys

# Control-plane subprocesses must not touch accelerators (children inherit).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


# --- shared relay-test helpers (test_proxy.py + test_native.py) ----------

import socketserver  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402


class EchoHandler(socketserver.BaseRequestHandler):
    """Upper-cases everything — relay tests assert bytes crossed both ways."""

    def handle(self):
        while True:
            data = self.request.recv(4096)
            if not data:
                return
            self.request.sendall(data.upper())


@pytest.fixture()
def echo_server():
    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), EchoHandler)
    srv.daemon_threads = True
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address[1]
    srv.shutdown()
    srv.server_close()


def recv_all(s):
    out = b""
    while True:
        chunk = s.recv(65536)
        if not chunk:
            return out
        out += chunk


# --- fake gsutil (gs:// store tests across modules) ----------------------

FAKE_GSUTIL = """#!/bin/bash
# fake gsutil: maps gs://<bucket>/<key> onto $FAKE_GCS_ROOT/<bucket>/<key>
set -e
cmd=$1; shift
map() { echo "$FAKE_GCS_ROOT/${1#gs://}"; }
unmap() { echo "gs://${1#"$FAKE_GCS_ROOT/"}"; }
case "$cmd" in
  cp)
    src=$1; dst=$2
    [[ $src == gs://* ]] && src=$(map "$src")
    if [[ $dst == gs://* ]]; then dst=$(map "$dst"); mkdir -p "$(dirname "$dst")"; fi
    cp "$src" "$dst"
    ;;
  ls)
    # wildcard form prints matching object URIs (recursive **), like the
    # real CLI; the plain form is an existence check
    if [[ $1 == *'*'* ]]; then
      shopt -s globstar nullglob
      mapped=$(map "$1")
      found=0
      for p in $mapped; do
        [[ -f $p ]] && { unmap "$p"; found=1; }
      done
      [[ $found == 1 ]] || { echo "CommandException: no URLs matched" >&2; exit 1; }
    else
      p=$(map "$1"); [[ -e $p ]] || { echo "CommandException: no URLs matched" >&2; exit 1; }
    fi
    ;;
  rm)
    # single-object delete (checkpoint retention GC); already-gone is
    # the real CLI's "No URLs matched" failure
    p=$(map "$1")
    [[ -f $p ]] || { echo "CommandException: No URLs matched" >&2; exit 1; }
    rm -f "$p"
    ;;
  *) echo "unsupported: $cmd" >&2; exit 2 ;;
esac
"""


@pytest.fixture
def fake_gcs(tmp_path, monkeypatch):
    """PATH-shimmed gsutil mirroring cp/ls onto a local dir; returns the
    backing root. The canned-fixture pattern for gs:// code paths."""
    import stat

    bindir = tmp_path / "bin"
    bindir.mkdir()
    gsutil = bindir / "gsutil"
    gsutil.write_text(FAKE_GSUTIL)
    gsutil.chmod(gsutil.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")
    monkeypatch.setenv("FAKE_GCS_ROOT", str(tmp_path / "gcs"))
    return tmp_path / "gcs"
