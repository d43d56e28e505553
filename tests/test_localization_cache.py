"""Content-addressed localization cache + persistent compile-cache
wiring (the other two legs of the cold-start demolition).

Pins the cache's correctness invariants — identical bytes land once
machine-wide, materialization is a hardlink, a killed fetch never leaves
a torn blob or a lying marker — plus the atomic store fetch idiom and
the `tony.executor.jax-cache-dir` → $TONY_JAX_CACHE_DIR env render the
trainer/serving engine consume.
"""

from __future__ import annotations

import glob
import os
import threading

import pytest

from tony_tpu import constants as C
from tony_tpu.conf import TonyConfiguration, keys as K
from tony_tpu.utils.localization import (
    LocalizationCache, localize_resource,
)

pytestmark = pytest.mark.warmpool


@pytest.fixture
def cache(tmp_path):
    return LocalizationCache(str(tmp_path / "cache"))


def _write(tmp_path, name: str, data: bytes) -> str:
    p = tmp_path / name
    p.write_bytes(data)
    return str(p)


def test_identical_bytes_stored_once(cache, tmp_path):
    a = _write(tmp_path, "a.bin", b"same-bytes")
    b = _write(tmp_path, "b.bin", b"same-bytes")
    other = _write(tmp_path, "c.bin", b"different")
    blob_a = cache.get_or_add_file(a)       # miss
    blob_b = cache.get_or_add_file(b)       # hit: same digest
    blob_c = cache.get_or_add_file(other)   # miss
    assert blob_a == blob_b != blob_c
    assert len(os.listdir(cache.by_digest)) == 2
    assert (cache.hits, cache.misses) == (1, 2)


def test_materialize_is_hardlink_and_overwrites_stale(cache, tmp_path):
    src = _write(tmp_path, "res.bin", b"payload")
    blob = cache.get_or_add_file(src)
    dest_dir = str(tmp_path / "container")
    os.makedirs(dest_dir)
    stale = os.path.join(dest_dir, "res.bin")
    with open(stale, "wb") as f:
        f.write(b"stale-from-a-previous-attempt")
    out = cache.materialize(blob, dest_dir, "res.bin")
    assert out == stale
    assert os.stat(out).st_ino == os.stat(blob).st_ino   # hardlink
    with open(out, "rb") as f:
        assert f.read() == b"payload"
    # no tmp debris from the atomic link+rename
    assert not glob.glob(os.path.join(dest_dir, "*.link-tmp-*"))


def test_concurrent_materialize_same_dest_is_safe(cache, tmp_path):
    """The width-k regression this fixes: k executors run as THREADS of
    one pool process, all materializing the same resource to the same
    path. Every thread must succeed (no tmp-name collision, no
    delete-under-a-neighbor) and the final file must be whole."""
    src = _write(tmp_path, "res.bin", b"x" * 65536)
    blob = cache.get_or_add_file(src)
    dest_dir = str(tmp_path / "shared_container")
    errors = []

    def _one():
        try:
            cache.materialize(blob, dest_dir, "res.bin")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=_one) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    with open(os.path.join(dest_dir, "res.bin"), "rb") as f:
        assert f.read() == b"x" * 65536


def test_stat_memo_hashes_each_source_once(cache, tmp_path, monkeypatch):
    """Digest memoization by (dev, ino, size, mtime_ns): hashing the
    source costs more than the copy the cache saves, so a width-k gang
    re-localizing one resource must sha256 it exactly once machine-wide
    — and an edited source (new mtime) must be re-hashed, never served
    stale."""
    from tony_tpu.utils import localization as loc

    real = loc._sha256_file
    hashed = []
    monkeypatch.setattr(loc, "_sha256_file",
                        lambda p: (hashed.append(p), real(p))[1])
    src = _write(tmp_path, "big.bin", b"r" * 4096)
    blob1 = cache.get_or_add_file(src)
    for _ in range(8):                       # the rest of the gang
        assert cache.get_or_add_file(src) == blob1
    assert len(hashed) == 1
    assert cache.hits == 8

    # a rewritten source is a different stat identity: re-hash, new blob
    os.utime(src, ns=(1, 1))   # force a distinct mtime_ns
    with open(src, "wb") as f:
        f.write(b"s" * 4096)
    blob2 = cache.get_or_add_file(src)
    assert blob2 != blob1 and len(hashed) == 2


def test_uri_fetched_once_machine_wide(cache):
    calls = []

    def fetcher(uri, dest):
        calls.append(uri)
        with open(dest, "wb") as f:
            f.write(b"remote-bytes")

    blob1 = cache.get_or_fetch_uri("gs://bucket/res", fetcher)
    blob2 = cache.get_or_fetch_uri("gs://bucket/res", fetcher)
    assert blob1 == blob2 and calls == ["gs://bucket/res"]
    with open(blob1, "rb") as f:
        assert f.read() == b"remote-bytes"


def test_failed_fetch_leaves_no_marker_no_blob(cache):
    def broken(uri, dest):
        with open(dest, "wb") as f:
            f.write(b"half-")
        raise OSError("connection reset")

    with pytest.raises(OSError):
        cache.get_or_fetch_uri("gs://bucket/flaky", broken)
    # nothing cached, nothing torn: the next attempt refetches
    assert os.listdir(cache.by_uri) == []
    assert os.listdir(cache.by_digest) == []
    assert not glob.glob(os.path.join(cache.root, ".fetch-tmp-*"))

    def working(uri, dest):
        with open(dest, "wb") as f:
            f.write(b"whole")

    blob = cache.get_or_fetch_uri("gs://bucket/flaky", working)
    with open(blob, "rb") as f:
        assert f.read() == b"whole"


def test_localize_resource_through_cache_dedups_copies(cache, tmp_path):
    src = _write(tmp_path, "data.txt", b"training-data")
    d1, d2 = str(tmp_path / "c1"), str(tmp_path / "c2")
    out1 = localize_resource(src, d1, cache=cache)
    out2 = localize_resource(src, d2, cache=cache)
    # both containers see the file; bytes exist once (3 links: blob + 2)
    assert os.stat(out1).st_ino == os.stat(out2).st_ino
    assert os.stat(out1).st_nlink == 3
    assert cache.hits >= 1


def test_from_conf_gating(tmp_path):
    conf = TonyConfiguration()
    assert LocalizationCache.from_conf(conf) is None   # default off
    conf.set(K.LOCALIZATION_CACHE_ENABLED, True, "test")
    conf.set(K.LOCALIZATION_CACHE_DIR, str(tmp_path / "locs"), "test")
    cache = LocalizationCache.from_conf(conf)
    assert cache is not None
    assert cache.root == str(tmp_path / "locs")


def test_local_store_fetch_is_atomic(tmp_path):
    from tony_tpu.storage import LocalDirStore

    store = LocalDirStore(str(tmp_path / "store"))
    uri = store.put(_write(tmp_path, "src.bin", b"stored-bytes"), "src.bin")
    dest = str(tmp_path / "out" / "src.bin")
    got = store.fetch(uri, dest)
    assert got == dest
    with open(dest, "rb") as f:
        assert f.read() == b"stored-bytes"
    # the download-to-tmp + rename idiom leaves no debris
    assert not glob.glob(f"{dest}.fetch-tmp-*")
    assert not glob.glob(os.path.join(str(tmp_path / "store"),
                                      "*.put-tmp-*"))


# ---------------------------------------------------------------------------
# persistent XLA compile cache wiring
# ---------------------------------------------------------------------------

class _FakeJaxConfig:
    def __init__(self):
        self.calls = {}

    def update(self, key, value):
        self.calls[key] = value


class _FakeJax:
    def __init__(self, backend="tpu"):
        self.config = _FakeJaxConfig()
        self._backend = backend

    def default_backend(self):
        return self._backend


def test_compile_cache_env_rendered_into_user_env():
    """tony.executor.jax-cache-dir lands in EVERY framework's user env
    as $TONY_JAX_CACHE_DIR — the trainer/serving engine pick it up."""
    from tony_tpu.executor.runtimes import render_framework_env

    spec = {"worker": ["h0:1000", "h1:1001"]}
    conf = TonyConfiguration()
    env = render_framework_env("jax", spec, "worker", 0, conf)
    assert C.JAX_CACHE_DIR not in env                  # knob unset
    conf.set(K.EXECUTOR_JAX_CACHE_DIR, "/var/cache/tony-jax", "test")
    env = render_framework_env("jax", spec, "worker", 0, conf)
    assert env[C.JAX_CACHE_DIR] == "/var/cache/tony-jax"
    # framework-independent: tensorflow tasks get it too
    env = render_framework_env("tensorflow", spec, "worker", 1, conf)
    assert env[C.JAX_CACHE_DIR] == "/var/cache/tony-jax"


@pytest.mark.parametrize("jax_env,conf_key", [
    (True, True), (False, True), (False, False),
], ids=["jax-env-set", "conf-key", "neither"])
def test_compile_cache_precedence(tmp_path, monkeypatch, jax_env, conf_key):
    """$JAX_COMPILATION_CACHE_DIR set -> jax reads it itself and NO code
    sets a directory; unset with the job's conf key -> the key's value;
    unset without -> the one fixed path inside the checkout."""
    from tony_tpu.utils import compilecache

    fixed = str(tmp_path / "checkout" / ".jax_cache")
    monkeypatch.setattr(compilecache, "CHECKOUT_CACHE_DIR", fixed)
    monkeypatch.delenv(compilecache.JAX_ENV, raising=False)
    monkeypatch.delenv(C.JAX_CACHE_DIR, raising=False)
    if jax_env:
        monkeypatch.setenv(compilecache.JAX_ENV, str(tmp_path / "outside"))
    if conf_key:
        monkeypatch.setenv(C.JAX_CACHE_DIR, str(tmp_path / "from_conf"))
    jax = _FakeJax()
    got = compilecache.enable_compile_cache(jax)
    cpu = _FakeJax(backend="cpu")
    if jax_env:
        # the user's own variable is jax's business on any backend
        assert compilecache.enable_compile_cache(cpu) == got
    else:
        # no default cache on the cpu backend: every hit there logs a
        # multi-kilobyte XLA machine-feature error
        assert compilecache.enable_compile_cache(cpu) is None
        assert cpu.config.calls == {}
    if jax_env:
        assert got == str(tmp_path / "outside")
        assert "jax_compilation_cache_dir" not in jax.config.calls
        assert not os.path.exists(tmp_path / "from_conf")
        assert not os.path.exists(fixed)
    else:
        want = str(tmp_path / "from_conf") if conf_key else fixed
        assert got == want
        assert jax.config.calls["jax_compilation_cache_dir"] == want
        assert os.path.isdir(want)
    # the thresholds are set either way: they are not a directory
    assert jax.config.calls[
        "jax_persistent_cache_min_compile_time_secs"] == 0.5


def test_checkout_cache_dir_is_fixed_under_the_checkout():
    """Computed from the package's own location — never the working
    directory (a container's is new on every submission), a temp name, a
    pid or a time: the path is part of the cache key."""
    from tony_tpu.utils.compilecache import CHECKOUT_CACHE_DIR

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert CHECKOUT_CACHE_DIR == os.path.join(repo, ".jax_cache")


def test_compile_cache_unwritable_dir_is_a_warning(tmp_path, monkeypatch):
    """The cache is an optimization, never a dependency: a directory that
    cannot be made (a read-only checkout) degrades to no cache."""
    from tony_tpu.utils import compilecache

    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.delenv(compilecache.JAX_ENV, raising=False)
    monkeypatch.setenv(C.JAX_CACHE_DIR, str(blocker / "sub"))
    jax = _FakeJax()
    assert compilecache.enable_compile_cache(jax) is None
    assert jax.config.calls == {}


def test_warm_pool_child_keeps_the_jax_cache_env(monkeypatch):
    """A warm executor scrubs inherited TONY_* and task-identity env
    before a bind; $JAX_COMPILATION_CACHE_DIR must survive it, or a
    warm-launched trainer would cache somewhere else than a cold one."""
    from tony_tpu.cluster import warmpool

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setenv("TONY_JAX_CACHE_DIR", "/stale/app/a")
    monkeypatch.setenv("JOB_NAME", "worker")
    warmpool._scrub_task_env()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    assert "TONY_JAX_CACHE_DIR" not in os.environ
    assert "JOB_NAME" not in os.environ
