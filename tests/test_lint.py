"""tonylint: the control-plane static-analysis pass (tools/tonylint/).

Three layers:

1. engine semantics — suppression syntax, shrink-only baseline,
   ``--changed`` against a synthetic git diff, output shapes;
2. per-rule fixtures — for every shipped rule: one offending snippet
   (fires), one clean snippet (silent), one suppressed snippet (silent,
   counted as suppressed);
3. the acceptance run — the full engine over tony_tpu/ at HEAD must be
   clean (modulo the checked-in, shrink-only baseline) and fast (<10 s
   — it IS a tier-1 test).

The legacy regex checks that tonylint subsumed keep one-line wrappers in
tests/test_logs.py / test_fleet.py / test_alerts.py, so tier-1 coverage
is unchanged.
"""

import json
import os
import subprocess
import time

import pytest

from tools.tonylint import (default_rules, findings_for, lint_repo,
                            repo_root, save_baseline)
from tools.tonylint.engine import (Project, apply_baseline, discover_files,
                                   load_baseline, run_rules)
from tools.tonylint.rules_conf import ConfigKeyRegistryRule
from tools.tonylint.rules_legacy import (AlertHotLoopRule,
                                         AlertRuleRegistryRule,
                                         GaugeRegistryRule, PrintBanRule,
                                         RendererCoverageRule)
from tools.tonylint.rules_locks import GuardedByRule, NoBlockingUnderLockRule
from tools.tonylint.rules_rpc import (AttemptFencingRule, RedactOnEgressRule,
                                      TracePropagationRule)
from tools.tonylint.rules_threads import ThreadHygieneRule

pytestmark = pytest.mark.lint

REPO = repo_root()


def _project(tmp_path, files: dict[str, str]) -> Project:
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)
    rels = [rel for rel in files if rel.endswith(".py")]
    return Project(str(tmp_path), rels)


def _run(tmp_path, files: dict[str, str], rules) -> list:
    report = run_rules(_project(tmp_path, files), list(rules))
    return report.findings


def _rule_ids(findings) -> list[str]:
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# guarded-by
# ---------------------------------------------------------------------------

GUARDED_OFFENDER = '''
import threading

class Store:
    def __init__(self):
        self._table = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    def get(self, k):
        return self._table.get(k)
'''

GUARDED_CLEAN = '''
import threading

class Store:
    def __init__(self):
        self._table = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    def get(self, k):
        with self._lock:
            return self._table.get(k)

    # holds: _lock (caller contract)
    def _get_locked(self, k):
        return self._table.get(k)
'''

GUARDED_SUPPRESSED = '''
import threading

class Store:
    def __init__(self):
        self._table = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    def peek(self, k):
        # tony: disable=guarded-by -- lock-free fast path, re-checked under lock
        return self._table.get(k)
'''


def test_guarded_by_fires_on_unlocked_access(tmp_path):
    findings = _run(tmp_path, {"tony_tpu/am/s.py": GUARDED_OFFENDER},
                    [GuardedByRule()])
    assert _rule_ids(findings) == ["guarded-by"]
    assert "_table" in findings[0].message


def test_guarded_by_silent_on_locked_access_and_holds_contract(tmp_path):
    assert _run(tmp_path, {"tony_tpu/am/s.py": GUARDED_CLEAN},
                [GuardedByRule()]) == []


def test_guarded_by_suppressed(tmp_path):
    project = _project(tmp_path, {"tony_tpu/am/s.py": GUARDED_SUPPRESSED})
    report = run_rules(project, [GuardedByRule()])
    assert report.findings == []
    assert report.suppressed == 1


def test_guarded_by_checks_methods_that_redeclare(tmp_path):
    """A method that RE-assigns an annotated attribute is still checked —
    resetting guarded state without the lock is exactly the bug class the
    rule exists for (it must not exempt the whole method)."""
    src = '''
import threading

class Store:
    def __init__(self):
        self._lock = threading.Lock()
        self._table = {}  # guarded-by: _lock
        self._count = 0  # guarded-by: _lock

    def reset(self):
        self._table = {}  # guarded-by: _lock
        self._count = 0
'''
    findings = _run(tmp_path, {"tony_tpu/am/s.py": src}, [GuardedByRule()])
    # both the unlocked re-declaration and the sibling write fire
    assert _rule_ids(findings) == ["guarded-by", "guarded-by"]
    assert {f.line for f in findings} == {11, 12}


def test_guarded_by_not_satisfied_by_another_objects_lock(tmp_path):
    """Holding a DIFFERENT object's same-named lock must not silence the
    rule — every class in this codebase calls its lock `_lock`, so the
    wrong-receiver case is exactly the missed-lock bug class (PR 11's
    note_full_serve) the rule exists for."""
    src = '''
import threading

class Store:
    def __init__(self):
        self._lock = threading.Lock()
        self._jobs = {}  # guarded-by: _lock
        self.peer = None

    def bad(self, k, v):
        with self.peer._lock:
            self._jobs[k] = v

    def good(self, k, v):
        with self._lock:
            self._jobs[k] = v
'''
    findings = _run(tmp_path, {"tony_tpu/am/s.py": src}, [GuardedByRule()])
    assert _rule_ids(findings) == ["guarded-by"]
    assert findings[0].line == 12


def test_guarded_by_subscripted_lock_table(tmp_path):
    src = '''
import threading

class Sharded:
    def __init__(self):
        # guarded-by: _locks
        self._shards = [{} for _ in range(4)]
        self._locks = [threading.Lock() for _ in range(4)]

    def good(self, idx, k):
        with self._locks[idx]:
            return self._shards[idx].get(k)

    def bad(self):
        return sum(len(s) for s in self._shards)
'''
    findings = _run(tmp_path, {"tony_tpu/am/shard.py": src},
                    [GuardedByRule()])
    assert len(findings) == 1 and findings[0].rule == "guarded-by"


# ---------------------------------------------------------------------------
# no-blocking-under-lock
# ---------------------------------------------------------------------------

BLOCKING_OFFENDER = '''
import threading
import time

class Sweeper:
    def __init__(self):
        self._lock = threading.Lock()

    def sweep(self):
        with self._lock:
            time.sleep(0.1)
'''

BLOCKING_CLEAN = '''
import threading
import time

class Sweeper:
    def __init__(self):
        self._lock = threading.Lock()

    def sweep(self):
        with self._lock:
            items = [1]
        time.sleep(0.1)
        return items
'''


def test_no_blocking_under_lock_fires_on_sleep(tmp_path):
    findings = _run(tmp_path, {"tony_tpu/am/x.py": BLOCKING_OFFENDER},
                    [NoBlockingUnderLockRule()])
    assert _rule_ids(findings) == ["no-blocking-under-lock"]


def test_no_blocking_under_lock_silent_outside_lock(tmp_path):
    assert _run(tmp_path, {"tony_tpu/am/x.py": BLOCKING_CLEAN},
                [NoBlockingUnderLockRule()]) == []


def test_no_blocking_under_lock_suppressed_and_rpc_methods(tmp_path):
    src = '''
import threading

class AM:
    def __init__(self, backend):
        self._lock = threading.Lock()
        self.backend = backend

    def drain(self):
        with self._lock:
            # tony: disable=no-blocking-under-lock -- justified here
            self.backend.stop_container("c1")

    def drain2(self):
        with self._lock:
            self.backend.stop_container("c2")

    def local_ok(self):
        with self._lock:
            self.update_metrics({})

    def update_metrics(self, req):
        return {}
'''
    project = _project(tmp_path, {"tony_tpu/am/y.py": src})
    report = run_rules(project, [NoBlockingUnderLockRule()])
    # drain2 fires (RPC-backed container stop under lock); drain is
    # suppressed; the direct self.update_metrics local call never fires
    assert len(report.findings) == 1
    assert report.findings[0].line and report.suppressed == 1


# ---------------------------------------------------------------------------
# attempt-fencing
# ---------------------------------------------------------------------------

FENCING_OFFENDER = '''
class Handler:
    def register_execution_result(self, req):
        task = self.session.get_task_by_id(req["task_id"])
        task.completed = True
        return {}
'''

FENCING_CLEAN = '''
class Handler:
    def register_execution_result(self, req):
        task = self.session.get_task_by_id(req["task_id"])
        attempt = int(req.get("task_attempt", -1))
        if attempt >= 0 and attempt != task.attempt:
            return {}
        task.completed = True
        return {}
'''


def test_attempt_fencing_fires_on_unfenced_handler(tmp_path):
    findings = _run(tmp_path, {"tony_tpu/am/h.py": FENCING_OFFENDER},
                    [AttemptFencingRule()])
    assert _rule_ids(findings) == ["attempt-fencing"]


def test_attempt_fencing_silent_on_fenced_handler(tmp_path):
    assert _run(tmp_path, {"tony_tpu/am/h.py": FENCING_CLEAN},
                [AttemptFencingRule()]) == []


def test_attempt_fencing_skips_abstract_and_out_of_scope(tmp_path):
    abstract = '''
import abc

class Iface(abc.ABC):
    @abc.abstractmethod
    def register_execution_result(self, req):
        """doc only"""
'''
    # abstract interface: silent; client stub dir: out of scope
    assert _run(tmp_path, {"tony_tpu/rpc/service.py": abstract,
                           "tony_tpu/rpc/client.py": FENCING_OFFENDER},
                [AttemptFencingRule()]) == []


def test_attempt_fencing_suppressed(tmp_path):
    src = FENCING_OFFENDER.replace(
        "    def register_execution_result",
        "    # tony: disable=attempt-fencing -- fenced by the caller\n"
        "    def register_execution_result")
    project = _project(tmp_path, {"tony_tpu/am/h.py": src})
    report = run_rules(project, [AttemptFencingRule()])
    assert report.findings == [] and report.suppressed == 1


# ---------------------------------------------------------------------------
# redact-on-egress
# ---------------------------------------------------------------------------

EGRESS_OFFENDER = '''
import json
import urllib.request

class PushSink:
    def deliver(self, payload):
        data = json.dumps(payload).encode()
        req = urllib.request.Request("http://hook", data=data)
        with urllib.request.urlopen(req, timeout=2):
            return True
'''

EGRESS_CLEAN = EGRESS_OFFENDER.replace(
    "data = json.dumps(payload).encode()",
    "data = json.dumps(redact_payload(payload)).encode()")


def test_redact_on_egress_fires_on_unredacted_sink(tmp_path):
    findings = _run(tmp_path, {"tony_tpu/observability/s.py": EGRESS_OFFENDER},
                    [RedactOnEgressRule()])
    assert _rule_ids(findings) == ["redact-on-egress"]


def test_redact_on_egress_silent_when_redacted(tmp_path):
    assert _run(tmp_path, {"tony_tpu/observability/s.py": EGRESS_CLEAN},
                [RedactOnEgressRule()]) == []


def test_redact_on_egress_suppressed(tmp_path):
    src = EGRESS_OFFENDER.replace(
        "    def deliver(self, payload):",
        "    # tony: disable=redact-on-egress -- payload pre-redacted upstream\n"
        "    def deliver(self, payload):")
    project = _project(tmp_path, {"tony_tpu/observability/s.py": src})
    report = run_rules(project, [RedactOnEgressRule()])
    assert report.findings == [] and report.suppressed == 1


TRACE_EXPORT_OFFENDER = '''
class ReqCollector:
    def export(self):
        return [dict(t) for t in self._done]


def write_serving_traces_file(history_dir, traces):
    with open(history_dir + "/serving_traces.json", "w") as f:
        f.write(str(traces))
'''

TRACE_EXPORT_CLEAN = TRACE_EXPORT_OFFENDER.replace(
    "return [dict(t) for t in self._done]",
    "return redact_traces([dict(t) for t in self._done])").replace(
    "f.write(str(traces))",
    "f.write(str(redact_traces(traces)))")


def test_redact_on_egress_covers_trace_export_surfaces(tmp_path):
    """Collector export/drain snapshots and the serving-traces history
    sidecar are operator-facing egress: both must redact."""
    findings = _run(tmp_path,
                    {"tony_tpu/observability/rt.py": TRACE_EXPORT_OFFENDER},
                    [RedactOnEgressRule()])
    assert _rule_ids(findings) == ["redact-on-egress"] * 2
    msgs = " | ".join(f.message for f in findings)
    assert "request-trace payloads" in msgs and "sidecar" in msgs
    assert _run(tmp_path,
                {"tony_tpu/observability/rt.py": TRACE_EXPORT_CLEAN},
                [RedactOnEgressRule()]) == []


# ---------------------------------------------------------------------------
# trace-propagation
# ---------------------------------------------------------------------------

TRACE_PROP_OFFENDER = '''
import urllib.request


class Frontend:
    def post_handoff(self, base, payload):
        rq = urllib.request.Request(base + "/v1/migrate", data=payload,
                                    headers={"Content-Type": "a/b"})
        return urllib.request.urlopen(rq, timeout=5)
'''

TRACE_PROP_CLEAN = TRACE_PROP_OFFENDER.replace(
    'headers={"Content-Type": "a/b"}',
    'headers={"X-Tony-Trace": ctx.header_value()}')

TRACE_PROP_CLEAN_ATTR = TRACE_PROP_OFFENDER.replace(
    'headers={"Content-Type": "a/b"}',
    'headers={reqtrace.HEADER: ctx.header_value()}')


def test_trace_propagation_fires_on_dropped_header(tmp_path):
    findings = _run(tmp_path, {"tony_tpu/serve/f.py": TRACE_PROP_OFFENDER},
                    [TracePropagationRule()])
    assert _rule_ids(findings) == ["trace-propagation"]
    assert "/v1/migrate" in findings[0].message


def test_trace_propagation_silent_when_header_forwarded(tmp_path):
    # both spellings satisfy: the literal header name or reqtrace.HEADER
    assert _run(tmp_path, {"tony_tpu/serve/f.py": TRACE_PROP_CLEAN},
                [TracePropagationRule()]) == []
    assert _run(tmp_path, {"tony_tpu/serve/f.py": TRACE_PROP_CLEAN_ATTR},
                [TracePropagationRule()]) == []


def test_trace_propagation_scoped_to_serve_and_data_plane(tmp_path):
    # outside tony_tpu/serve/: silent (webhook sinks etc. are not hops
    # of a request trace); non-data-plane URLs: silent
    assert _run(tmp_path, {"tony_tpu/am/f.py": TRACE_PROP_OFFENDER},
                [TracePropagationRule()]) == []
    other = TRACE_PROP_OFFENDER.replace("/v1/migrate", "/v1/load")
    assert _run(tmp_path, {"tony_tpu/serve/f.py": other},
                [TracePropagationRule()]) == []


def test_trace_propagation_suppressed(tmp_path):
    src = TRACE_PROP_OFFENDER.replace(
        '        rq = urllib.request.Request(',
        '        # tony: disable=trace-propagation -- loopback self-probe\n'
        '        rq = urllib.request.Request(')
    project = _project(tmp_path, {"tony_tpu/serve/f.py": src})
    report = run_rules(project, [TracePropagationRule()])
    assert report.findings == [] and report.suppressed == 1


# ---------------------------------------------------------------------------
# config-key-registry
# ---------------------------------------------------------------------------

MINI_KEYS = '''
TONY_PREFIX = "tony."
AM_MEMORY = "tony.am.memory"
UNUSED_KEY = "tony.am.unused-key"

RESERVED_SEGMENTS = frozenset({"am", "task", "queues"})


def jobtype_key(jobtype, attr):
    return f"{TONY_PREFIX}{jobtype}.{attr}"


def instances_key(jobtype):
    return jobtype_key(jobtype, "instances")


def queue_max_tpus_key(queue):
    return f"tony.queues.{queue}.max-tpus"
'''

MINI_DOCS = "| `tony.am.memory` | `'2g'` |\n"


def _conf_files(user_src: str) -> dict[str, str]:
    return {"tony_tpu/conf/keys.py": MINI_KEYS,
            "tony_tpu/am/user.py": user_src,
            "docs/configuration.md": MINI_DOCS}


def test_config_key_registry_fires_on_stray_and_reserved(tmp_path):
    user = '''
A = "tony.am.memory"          # registered: fine
B = "tony.worker.instances"   # dynamic jobtype shape: fine
C = "tony.queues.qa.max-tpus" # dynamic queue shape: fine
D = "tony.task.comand"        # reserved segment typo: FIRES
E = "tony.made.up-key"        # unknown shape: FIRES
'''
    findings = _run(tmp_path, _conf_files(user), [ConfigKeyRegistryRule()])
    msgs = " | ".join(f.message for f in findings)
    assert "tony.task.comand" in msgs and "tony.made.up-key" in msgs
    # UNUSED_KEY is defined but never referenced, and undocumented
    assert sum("UNUSED_KEY" in f.message for f in findings) == 2
    assert len(findings) == 4


def test_config_key_registry_clean(tmp_path):
    user = 'A = "tony.am.memory"\nB = UNUSED_KEY\n'
    docs = MINI_DOCS + "| `tony.am.unused-key` | x |\n"
    files = _conf_files(user)
    files["docs/configuration.md"] = docs
    assert _run(tmp_path, files, [ConfigKeyRegistryRule()]) == []


def test_config_key_registry_suppressed(tmp_path):
    user = ('# tony: disable=config-key-registry -- not a conf key\n'
            'D = "tony.not.a-key"\nB = UNUSED_KEY\nA = AM_MEMORY\n')
    files = _conf_files(user)
    files["docs/configuration.md"] = (
        MINI_DOCS + "| `tony.am.unused-key` | x |\n")
    project = _project(tmp_path, files)
    report = run_rules(project, [ConfigKeyRegistryRule()])
    assert report.findings == [] and report.suppressed == 1


# ---------------------------------------------------------------------------
# thread-hygiene
# ---------------------------------------------------------------------------

THREAD_OFFENDER = '''
import threading


def fire_and_forget(fn):
    threading.Thread(target=fn).start()


def swallow():
    try:
        fn()
    except Exception:
        pass


def bare():
    try:
        fn()
    except:
        return None
'''

THREAD_CLEAN = '''
import logging
import threading

LOG = logging.getLogger(__name__)


class Worker:
    def __init__(self):
        self._thread = threading.Thread(target=self._run, daemon=True)

    def stop(self):
        self._thread.join(timeout=2)


def careful():
    try:
        fn()
    except OSError:
        pass  # narrow catch on a best-effort path: deliberate
    try:
        fn()
    except Exception:
        LOG.debug("fn failed", exc_info=True)
'''


def test_thread_hygiene_fires(tmp_path):
    findings = _run(tmp_path, {"tony_tpu/am/t.py": THREAD_OFFENDER},
                    [ThreadHygieneRule()])
    assert _rule_ids(findings) == ["thread-hygiene"] * 3


def test_thread_hygiene_clean(tmp_path):
    assert _run(tmp_path, {"tony_tpu/am/t.py": THREAD_CLEAN},
                [ThreadHygieneRule()]) == []


def test_thread_subclass_not_fooled_by_str_join_or_daemon_comment(tmp_path):
    """The daemon/join evidence is AST shape, not text: a `", ".join(...)`
    in the module or a comment mentioning 'daemon' must not satisfy the
    subclass check, while `self.daemon = True` / a real `.join()` do."""
    offender = '''
import threading

class W(threading.Thread):
    # not a daemon on purpose? then someone must join it
    def run(self):
        print(", ".join(["a", "b"]))
'''
    findings = _run(tmp_path, {"tony_tpu/am/w.py": offender},
                    [ThreadHygieneRule()])
    assert "W(threading.Thread)" in findings[0].message
    clean_daemon = offender.replace(
        "    def run(self):",
        "    def __init__(self):\n"
        "        super().__init__(daemon=True)\n\n"
        "    def run(self):")
    assert _run(tmp_path, {"tony_tpu/am/w.py": clean_daemon},
                [ThreadHygieneRule()]) == []
    clean_joined = offender + "\n\ndef stop(w):\n    w.join(timeout=2)\n"
    assert _run(tmp_path, {"tony_tpu/am/w.py": clean_joined},
                [ThreadHygieneRule()]) == []
    # a VARIABLE-receiver string join (`sep.join(parts)`) is not reaping
    # evidence either — str.join always takes an iterable positional
    # arg, Thread.join never does
    var_join = offender + "\n\ndef render(sep, parts):\n" \
                          "    return sep.join(parts)\n"
    findings = _run(tmp_path, {"tony_tpu/am/w.py": var_join},
                    [ThreadHygieneRule()])
    assert "W(threading.Thread)" in findings[0].message


def test_thread_daemon_set_after_construction_is_clean(tmp_path):
    """`t = Thread(...); t.daemon = True; t.start()` is the stdlib's own
    documented idiom — it must not fire. Only a literal True counts:
    `t.daemon = False` is an explicit non-daemon and still fires."""
    clean = '''
import threading

def spin(fn):
    t = threading.Thread(target=fn)
    t.daemon = True
    t.start()

class Mgr:
    def start(self):
        self._worker = threading.Thread(target=self._run)
        self._worker.setDaemon(True)
        self._worker.start()
'''
    assert _run(tmp_path, {"tony_tpu/am/d.py": clean},
                [ThreadHygieneRule()]) == []
    explicit_non_daemon = clean.replace("t.daemon = True",
                                        "t.daemon = False")
    findings = _run(tmp_path, {"tony_tpu/am/d.py": explicit_non_daemon},
                    [ThreadHygieneRule()])
    assert _rule_ids(findings) == ["thread-hygiene"]


def test_thread_join_evidence_is_ast_not_text(tmp_path):
    """A comment or log string mentioning `.join(` must not exempt a
    directly-constructed non-daemon thread; a real `.join()` call on the
    assignment target does."""
    offender = '''
import threading

class Mgr:
    def start(self):
        # the caller must self._worker.join() eventually
        self._worker = threading.Thread(target=self._run)
        self._worker.start()
'''
    findings = _run(tmp_path, {"tony_tpu/am/m.py": offender},
                    [ThreadHygieneRule()])
    assert _rule_ids(findings) == ["thread-hygiene"]
    joined = offender + "\n    def stop(self):\n        self._worker.join()\n"
    assert _run(tmp_path, {"tony_tpu/am/m.py": joined},
                [ThreadHygieneRule()]) == []


def test_thread_hygiene_suppressed(tmp_path):
    src = THREAD_OFFENDER.replace(
        "    threading.Thread(target=fn).start()",
        "    # tony: disable=thread-hygiene -- reaped by the harness\n"
        "    threading.Thread(target=fn).start()").replace(
        "    except Exception:",
        "    # tony: disable=thread-hygiene -- nothing to log mid-exit\n"
        "    except Exception:").replace(
        "    except:",
        "    # tony: disable=thread-hygiene -- legacy shim\n"
        "    except:")
    project = _project(tmp_path, {"tony_tpu/am/t.py": src})
    report = run_rules(project, [ThreadHygieneRule()])
    assert report.findings == [] and report.suppressed == 3


# ---------------------------------------------------------------------------
# migrated legacy rules (fixture level; the original test files keep
# one-line wrappers running these over the real repo)
# ---------------------------------------------------------------------------

def test_print_ban_fires_and_log_ok_escapes(tmp_path):
    src = '''
def noisy():
    print("hello")


def marker():
    # log-ok: deliberate greppable bring-up line
    print("BRINGUP host ready")
'''
    findings = _run(tmp_path, {"tony_tpu/am/p.py": src}, [PrintBanRule()])
    assert len(findings) == 1 and findings[0].line == 3
    # out-of-scope dirs (train/) are not print-banned
    assert _run(tmp_path, {"tony_tpu/train/p.py": src},
                [PrintBanRule()]) == []


def test_print_ban_suppressed(tmp_path):
    src = ('def noisy():\n'
           '    # tony: disable=print-ban -- CLI surface\n'
           '    print("hello")\n')
    project = _project(tmp_path, {"tony_tpu/serve/p.py": src})
    report = run_rules(project, [PrintBanRule()])
    assert report.findings == [] and report.suppressed == 1


def test_gauge_registry_fixture(tmp_path):
    am = '''
GOOD = "tony_job_goodput_pct"
BAD = "tony_job_not_registered"
name = f"tony_job_{suffix}"
'''
    rule = GaugeRegistryRule(job_gauges={"tony_job_goodput_pct"},
                             step_time_gauges={})
    findings = _run(
        tmp_path, {"tony_tpu/am/application_master.py": am}, [rule])
    msgs = " | ".join(f.message for f in findings)
    assert "tony_job_not_registered" in msgs
    assert "f-string" in msgs
    assert len(findings) == 2
    # clean AM: silent
    rule2 = GaugeRegistryRule(job_gauges={"tony_job_goodput_pct"},
                              step_time_gauges={})
    assert _run(tmp_path, {
        "tony_tpu/am/application_master.py": 'G = "tony_job_goodput_pct"\n'},
        [rule2]) == []


def test_alert_rule_registry_fixture(tmp_path):
    am = 'RULES = ["train.goodput_floor", "train.not_a_rule"]\n'
    rule = AlertRuleRegistryRule(builtin_rules={"train.goodput_floor"})
    findings = _run(
        tmp_path, {"tony_tpu/am/application_master.py": am}, [rule])
    assert len(findings) == 1 and "train.not_a_rule" in findings[0].message


def test_alert_hot_loop_fixture(tmp_path):
    files = {
        "tony_tpu/am/application_master.py": "def _check_alerts(): pass\n",
        "tony_tpu/observability/fleet.py":
            "x = 'alert_engine.evaluate'\n",
        "tony_tpu/train/hot.py": "from x import AlertEngine\n",
    }
    findings = _run(tmp_path, files, [AlertHotLoopRule()])
    assert len(findings) == 1
    assert findings[0].path == "tony_tpu/train/hot.py"
    files["tony_tpu/train/hot.py"] = "pass\n"
    assert _run(tmp_path, files, [AlertHotLoopRule()]) == []


def test_renderer_coverage_fires_on_missing_renderer(monkeypatch):
    from tony_tpu.events import render
    missing = dict(render.RENDERERS)
    removed = next(iter(missing))
    del missing[removed]
    monkeypatch.setattr(render, "RENDERERS", missing)
    project = Project(REPO, ["tony_tpu/events/render.py"])
    report = run_rules(project, [RendererCoverageRule()])
    assert any(removed.value in f.message for f in report.findings)


# ---------------------------------------------------------------------------
# engine semantics: suppressions, baseline, --changed, output
# ---------------------------------------------------------------------------

def test_baseline_shrink_only_semantics(tmp_path):
    offender = {"tony_tpu/am/s.py": GUARDED_OFFENDER}
    findings = _run(tmp_path, offender, [GuardedByRule()])
    assert len(findings) == 1
    baseline_path = tmp_path / "baseline.json"
    save_baseline(str(baseline_path), findings, why="fixture debt")
    baseline = load_baseline(str(baseline_path))
    # exact coverage: accepted as debt, nothing new, nothing stale
    new, stale = apply_baseline(findings, baseline)
    assert new == [] and stale == []
    # a SECOND finding in the same bucket is new debt -> fails
    twice = findings + findings
    new, stale = apply_baseline(twice, baseline)
    assert len(new) == 1 and stale == []
    # the finding was fixed but the entry remains -> stale -> fails
    new, stale = apply_baseline([], baseline)
    assert new == [] and len(stale) == 1 and "shrink" in stale[0]


def test_checked_in_baseline_is_loadable_and_documented():
    baseline = load_baseline(os.path.join(REPO, "tools",
                                          "lint_baseline.json"))
    # every entry (if any) carries a one-line justification
    for key, entry in baseline.items():
        assert "::" in key
        assert entry.get("why"), f"baseline entry {key} has no justification"
        assert int(entry.get("count", 0)) >= 1


def test_changed_mode_against_synthetic_git_diff(tmp_path):
    """--changed restricts per-file rules to git-touched files;
    project-wide rules still run."""
    repo = tmp_path / "repo"
    (repo / "tony_tpu" / "am").mkdir(parents=True)
    (repo / "tony_tpu" / "am" / "a.py").write_text(GUARDED_OFFENDER)
    (repo / "tony_tpu" / "am" / "b.py").write_text(
        GUARDED_OFFENDER.replace("Store", "Other"))
    env = {**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}
    for cmd in (["git", "init", "-q"], ["git", "add", "-A"],
                ["git", "commit", "-qm", "seed"]):
        subprocess.run(cmd, cwd=repo, env=env, check=True,
                       capture_output=True)
    # touch ONLY b.py
    (repo / "tony_tpu" / "am" / "b.py").write_text(
        GUARDED_OFFENDER.replace("Store", "Other") + "\n# touched\n")
    report = lint_repo(str(repo), rules=[GuardedByRule()],
                       changed=True, baseline_path=os.devnull)
    assert {f.path for f in report.findings} == {"tony_tpu/am/b.py"}
    # without --changed both files fire
    report = lint_repo(str(repo), rules=[GuardedByRule()],
                       changed=False, baseline_path=os.devnull)
    assert {f.path for f in report.findings} == {"tony_tpu/am/a.py",
                                                 "tony_tpu/am/b.py"}


def test_changed_mode_with_root_below_git_toplevel(tmp_path):
    """A project root NESTED below the git toplevel (vendored checkout)
    must still match its touched files — without `git diff --relative`
    the diff emits toplevel-relative paths that never intersect the
    project relpaths, and the gate silently checks zero files."""
    (tmp_path / "vendor" / "tony_tpu" / "am").mkdir(parents=True)
    target = tmp_path / "vendor" / "tony_tpu" / "am" / "a.py"
    target.write_text(GUARDED_CLEAN)
    env = {**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}
    for cmd in (["git", "init", "-q"], ["git", "add", "-A"],
                ["git", "commit", "-qm", "seed"]):
        subprocess.run(cmd, cwd=tmp_path, env=env, check=True,
                       capture_output=True)
    target.write_text(GUARDED_OFFENDER)
    report = lint_repo(str(tmp_path / "vendor"), rules=[GuardedByRule()],
                       changed=True, baseline_path=os.devnull)
    assert {f.path for f in report.findings} == {"tony_tpu/am/a.py"}


def test_update_baseline_rejects_any_subset_scan(tmp_path):
    """--update-baseline with --changed, --rules, or a positional path
    subset would rewrite the WHOLE baseline from a partial scan,
    silently deleting every unscanned bucket's accepted debt — all
    three exit 2 without touching the file."""
    from tools.tonylint.__main__ import main
    (tmp_path / "tony_tpu" / "am").mkdir(parents=True)
    (tmp_path / "tony_tpu" / "am" / "s.py").write_text(GUARDED_OFFENDER)
    for extra in (["--changed"], ["--rules", "guarded-by"], ["tony_tpu/am"]):
        assert main(["--root", str(tmp_path), "--update-baseline",
                     *extra]) == 2
    assert not (tmp_path / "tools" / "lint_baseline.json").exists()


def test_update_baseline_preserves_hand_written_why(tmp_path):
    """The documented workflow adds one-line justifications by hand
    after generation; a later full --update-baseline (debt shrank
    elsewhere) must keep the surviving buckets' `why`."""
    from tools.tonylint.engine import Finding
    path = str(tmp_path / "baseline.json")
    f = Finding("guarded-by", "tony_tpu/am/s.py", 9, "msg")
    save_baseline(path, [f])
    data = json.loads(open(path).read())
    data["entries"][f.key]["why"] = "lock-free fast path, re-checked"
    with open(path, "w") as fh:
        json.dump(data, fh)
    save_baseline(path, [f])
    kept = json.loads(open(path).read())["entries"][f.key]["why"]
    assert kept == "lock-free fast path, re-checked"


def test_report_shapes_and_cli_exit_codes(tmp_path):
    (tmp_path / "tony_tpu" / "am").mkdir(parents=True)
    (tmp_path / "tony_tpu" / "am" / "s.py").write_text(GUARDED_OFFENDER)
    report = lint_repo(str(tmp_path), rules=[GuardedByRule()],
                       baseline_path=os.devnull)
    assert not report.ok
    payload = report.to_dict()
    assert payload["findings"][0]["rule"] == "guarded-by"
    assert "guarded-by" in report.render()
    # CLI contract: nonzero on findings, zero when clean
    from tools.tonylint.__main__ import main
    assert main(["--root", str(tmp_path), "--rules", "guarded-by"]) == 1
    (tmp_path / "tony_tpu" / "am" / "s.py").write_text(GUARDED_CLEAN)
    assert main(["--root", str(tmp_path), "--rules", "guarded-by"]) == 0


def test_parse_error_becomes_a_finding(tmp_path):
    findings = _run(tmp_path, {"tony_tpu/am/broken.py": "def f(:\n"},
                    [GuardedByRule()])
    assert _rule_ids(findings) == ["parse-error"]


def test_crashed_rule_becomes_a_finding_not_a_traceback(tmp_path):
    """A rule that raises (e.g. a registry rule importing a syntax-broken
    live module) must surface as a finding in the report — --json
    consumers and the pre-commit gate never see a raw traceback."""
    from tools.tonylint.engine import Rule

    class Exploding(Rule):
        id = "exploding"
        description = "always raises"

        def run(self, project):
            raise ImportError("live module is broken")

    project = _project(tmp_path, {"tony_tpu/am/ok.py": "X = 1\n"})
    report = run_rules(project, [Exploding(), GuardedByRule()])
    assert _rule_ids(report.findings) == ["exploding"]
    assert "rule crashed" in report.findings[0].message
    assert not report.ok


def test_wildcard_suppression(tmp_path):
    src = GUARDED_OFFENDER.replace(
        "        return self._table.get(k)",
        "        # tony: disable=* -- everything deliberate on this line\n"
        "        return self._table.get(k)")
    project = _project(tmp_path, {"tony_tpu/am/s.py": src})
    report = run_rules(project, [GuardedByRule()])
    assert report.findings == [] and report.suppressed == 1


def test_changed_mode_fails_loudly_when_git_fails(tmp_path):
    """--changed must never report clean because git failed — zero files
    checked is a pass exactly when it must not be."""
    from tools.tonylint.engine import GitError, changed_files
    from tools.tonylint.__main__ import main
    (tmp_path / "tony_tpu" / "am").mkdir(parents=True)
    (tmp_path / "tony_tpu" / "am" / "s.py").write_text(GUARDED_OFFENDER)
    with pytest.raises(GitError):
        changed_files(str(tmp_path))  # not a git repo
    assert main(["--root", str(tmp_path), "--changed",
                 "--rules", "guarded-by"]) == 2


# ---------------------------------------------------------------------------
# acceptance: the full pass over the repo at HEAD
# ---------------------------------------------------------------------------

def test_repo_is_clean_under_the_full_rule_set_within_budget():
    """`python -m tools.tonylint tony_tpu/` exits 0 at HEAD with the
    checked-in (shrink-only) baseline, in under 10 s of this process's
    own CPU time — the tier-1 gate the ISSUE pins; the wall clock of a
    host shared with the other test workers read 12.3 s for a pass that
    takes 4.2 s alone."""
    t0 = time.process_time()
    report = lint_repo(REPO)
    elapsed = time.process_time() - t0
    assert report.ok, "\n" + report.render()
    assert report.checked_files > 80
    assert {r.id for r in default_rules()} == set(report.rules)
    assert elapsed < 10.0, f"lint pass took {elapsed:.1f}s (budget 10s)"


def test_findings_for_wrapper_surface():
    """The one-line wrapper the migrated legacy tests call."""
    assert findings_for("print-ban") == []
    assert json.loads(json.dumps(lint_repo(
        REPO, rule_filter=lambda r: r.id == "print-ban").to_dict()))["ok"]


def test_findings_for_is_not_satisfied_by_a_baseline_entry(tmp_path,
                                                           monkeypatch):
    """The wrappers are the tier-1 hard assertions the pre-migration
    regex checks were: a tools/lint_baseline.json entry absorbing a
    violation must NOT make findings_for() report clean."""
    import tools.tonylint as tl
    (tmp_path / "tony_tpu" / "am").mkdir(parents=True)
    (tmp_path / "tony_tpu" / "am" / "p.py").write_text(
        'def f():\n    print("x")\n')
    (tmp_path / "tools").mkdir()
    offending = lint_repo(str(tmp_path), baseline_path=os.devnull,
                          rule_filter=lambda r: r.id == "print-ban")
    save_baseline(str(tmp_path / "tools" / "lint_baseline.json"),
                  offending.findings, why="trying to hide debt")
    # the CLI honors the baseline...
    baselined = lint_repo(str(tmp_path),
                          rule_filter=lambda r: r.id == "print-ban")
    assert baselined.ok and baselined.baselined == 1
    # ...but the wrapper surface does not
    monkeypatch.setattr(tl, "repo_root", lambda: str(tmp_path))
    tl._repo_report.cache_clear()
    try:
        assert len(tl.findings_for("print-ban")) == 1
    finally:
        tl._repo_report.cache_clear()
