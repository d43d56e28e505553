"""config-key-registry: the 149-key `tony.*` registry must stay closed.

conf/keys.py is the single source of truth for configuration key names
(the reference's TonyConfigurationKeys.java); docs/configuration.md is
generated from it (tools/gen_config_docs.py) and tests/test_conf.py
pins the generated file. What nothing checked until now: stray literals.
A `conf.get_str("tony.task.comand")` typo — or a key invented inline and
never registered — read as "unset" forever and no test noticed.

The rule closes the loop, all statically (keys.py is PARSED, never
imported, so the lint can run against a broken tree):

- every `tony.*` string literal in tony_tpu/ must be a registered static
  key, or match a dynamic builder shape (`tony.<jobtype>.<attr>` for the
  attrs keys.py's jobtype_key helpers define, `tony.queues.<q>.<attr>`
  for the queue-hierarchy helpers) with the jobtype segment outside
  RESERVED_SEGMENTS;
- reserved segments are respected: `tony.<reserved>.<x>` literals must
  be exact registered keys, never dynamic matches;
- profiler span names (`tony.engine.*`, `tony.frontend.*`) are not keys
  and are passed over;
- every registered key is documented in docs/configuration.md;
- every registered key constant is referenced somewhere outside keys.py
  (a key nothing reads is dead weight or a rename's orphan).
"""

from __future__ import annotations

import ast
import re
from typing import Iterable, Optional

from tools.tonylint.engine import Finding, Project, PyFile, Rule

KEYS_FILE = "tony_tpu/conf/keys.py"
DOCS_FILE = "docs/configuration.md"
KEY_LITERAL_RE = re.compile(r"^tony\.[a-z][a-z0-9_.\-]*$")
# profiler span names (observability/spans.py: `tony.engine.*` on the
# serving loop's thread, `tony.frontend.*` on its handler threads) share
# the prefix and are not configuration keys
SPAN_PREFIXES = ("tony.engine.", "tony.frontend.")


class KeyRegistry:
    """Parsed view of conf/keys.py: static keys, reserved segments, and
    the dynamic per-jobtype / per-queue attribute shapes derived from
    the helper functions themselves (the registry stays self-describing
    — a new helper is picked up without touching the lint)."""

    def __init__(self, tree: ast.Module):
        self.static: dict[str, str] = {}       # literal -> CONSTANT_NAME
        self.const_lines: dict[str, int] = {}  # CONSTANT_NAME -> lineno
        self.reserved: set[str] = set()
        self.jobtype_attrs: set[str] = set()
        self.queue_attrs: set[str] = set()
        for node in tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                if isinstance(node.value, ast.Constant) \
                        and isinstance(node.value.value, str) \
                        and node.value.value.startswith("tony."):
                    self.static[node.value.value] = name
                    self.const_lines[name] = node.lineno
                elif name == "RESERVED_SEGMENTS":
                    for child in ast.walk(node.value):
                        if isinstance(child, ast.Constant) \
                                and isinstance(child.value, str):
                            self.reserved.add(child.value)
            elif isinstance(node, ast.FunctionDef):
                self._harvest_helper(node)

    def _harvest_helper(self, fn: ast.FunctionDef) -> None:
        for child in ast.walk(fn):
            if not isinstance(child, ast.Return) or child.value is None:
                continue
            val = child.value
            # return jobtype_key(jobtype, "attr")
            if isinstance(val, ast.Call) and isinstance(val.func, ast.Name) \
                    and val.func.id == "jobtype_key" and len(val.args) == 2 \
                    and isinstance(val.args[1], ast.Constant):
                self.jobtype_attrs.add(str(val.args[1].value))
            # return f"tony.queues.{queue}.<attr>"
            elif isinstance(val, ast.JoinedStr):
                parts = [p.value for p in val.values
                         if isinstance(p, ast.Constant)]
                text = "".join(str(p) for p in parts)
                if text.startswith("tony.queues.") and text.count(".") >= 3:
                    self.queue_attrs.add(text.rsplit(".", 1)[-1])

    def classify(self, literal: str) -> Optional[str]:
        """None when the literal is a legitimate key; else the problem."""
        if literal in self.static:
            return None
        parts = literal.split(".")
        if len(parts) < 2 or not parts[-1]:
            return "malformed tony.* key"
        segment = parts[1]
        if segment == "queues":
            if len(parts) >= 4 and ".".join(parts[3:]) in self.queue_attrs:
                return None
            return (f"unknown queue-hierarchy key (expected "
                    f"tony.queues.<q>.<{'|'.join(sorted(self.queue_attrs))}>)")
        if segment in self.reserved:
            return (f"not in conf/keys.py and '{segment}' is a reserved "
                    f"segment (typo, or register the key)")
        if len(parts) >= 3 and ".".join(parts[2:]) in self.jobtype_attrs:
            return None  # dynamic tony.<jobtype>.<attr>
        return ("not a registered key and not a dynamic "
                "tony.<jobtype>.<attr> shape — register it in conf/keys.py")


def _string_literals(pf: PyFile) -> Iterable[tuple[int, str]]:
    """(line, value) for plain string constants, skipping docstrings —
    prose ABOUT keys must not count as key usage (or misusage)."""
    doc_lines: set[int] = set()
    for node in ast.walk(pf.tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr) \
                    and isinstance(body[0].value, ast.Constant) \
                    and isinstance(body[0].value.value, str):
                doc_lines.add(body[0].value.lineno)
    for node in ast.walk(pf.tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and node.lineno not in doc_lines:
            yield node.lineno, node.value


class ConfigKeyRegistryRule(Rule):
    id = "config-key-registry"
    description = ("every tony.* literal resolves against conf/keys.py "
                   "(+ dynamic shapes); every registered key is referenced "
                   "and documented in docs/configuration.md")
    project_wide = True

    def run(self, project: Project) -> Iterable[Finding]:
        keys_pf = project.file(KEYS_FILE)
        if keys_pf is None:
            return
        registry = KeyRegistry(keys_pf.tree)
        docs = project.read_text(DOCS_FILE) or ""
        # 1) stray / drifted literals anywhere in the package
        for pf in project.files:
            if pf.relpath == KEYS_FILE:
                continue
            for line, value in _string_literals(pf):
                if not KEY_LITERAL_RE.match(value) \
                        or value.startswith(SPAN_PREFIXES):
                    continue
                problem = registry.classify(value)
                if problem:
                    yield Finding(self.id, pf.relpath, line,
                                  f'"{value}": {problem}')
        # 2) registered keys must be documented + referenced
        corpus = "\n".join(pf.source for pf in project.files
                           if pf.relpath != KEYS_FILE)
        for literal, const in sorted(registry.static.items()):
            lineno = registry.const_lines.get(const, 1)
            if docs and literal not in docs:
                yield Finding(
                    self.id, KEYS_FILE, lineno,
                    f"{const} = \"{literal}\" is not documented in "
                    f"{DOCS_FILE} — regenerate it "
                    f"(python tools/gen_config_docs.py)")
            if not re.search(rf"\b{re.escape(const)}\b", corpus) \
                    and literal not in corpus:
                yield Finding(
                    self.id, KEYS_FILE, lineno,
                    f"{const} = \"{literal}\" is defined but never "
                    f"referenced anywhere in tony_tpu/ — dead key or "
                    f"rename orphan")
