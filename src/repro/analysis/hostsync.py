"""R4 host-sync detector: the hot loop never blocks on the device.

Origin: PR2/PR3 (async dispatch pipeline) — the engine's throughput rests
on the host scheduler running AHEAD of the device: every jit dispatch is
async, generated tokens are read back only at ``_harvest`` boundaries,
and the decode feedback loop stays device-resident (``last_tok``).  One
``.item()`` / ``np.asarray`` / implicit ``bool`` on a device array inside
the scheduling path serializes host and device and the dispatch-bound
soft spot returns.

This is an AST scan of the engine source (no execution): within the
hot-loop methods it tracks which expressions are device-rooted —
``self.last_tok`` / ``self.cache`` / ``self._sample_key`` and any local
assigned from a ``self._jit_*`` call — and flags

  * ``.item()`` on a device-rooted expression;
  * ``np.**(device_rooted)`` / ``jax.device_get(...)`` / builtin
    ``int/float/bool(device_rooted)`` — forced transfers;
  * ``if``/``while`` tests on a device-rooted expression (implicit
    ``__bool__`` blocks);
  * ``.block_until_ready()`` not guarded by an ``async_steps`` check
    (the documented opt-in sync point).

``_harvest`` is the allowed boundary and is not scanned.  PR7 adds a
second documented boundary: ``_quarantine_check`` — the NaN-guard's
per-step finiteness readback (``EngineConfig.nan_guard``), an explicit
opt-in sync exactly like ``async_steps=False`` — and extends the scanned
set with the scheduler's preempt/restore/cancel/growth methods, which
must stay pure host bookkeeping (they run inside the admission path of
every iteration).
"""
from __future__ import annotations

import ast
import inspect
import textwrap

from repro.analysis.framework import Rule

HOT_METHODS = ("step", "_step_unified", "_schedule", "_admit",
               "_admit_batched", "_admit_sequential", "_admit_paged",
               "_post_admit", "_release_slot", "_prefix_insert",
               "_next_step_idx",
               # PR7 resilience layer: scheduling decisions are host-only
               "_ensure_decode_page", "_preempt_slot", "_running_rows",
               "_covered", "preempt", "cancel", "_terminate_slot",
               "_finish_slot", "_sweep_deadlines", "_quarantine")
DEVICE_ATTRS = ("last_tok", "cache", "_sample_key")
_FORCING_BUILTINS = ("int", "float", "bool")


def _engine_source() -> str:
    from repro.serving import engine
    return inspect.getsource(engine)


class _MethodScan(ast.NodeVisitor):
    def __init__(self, rule, method: str, device_attrs):
        self.rule = rule
        self.method = method
        self.device_attrs = device_attrs
        self.tainted: set = set()
        self.findings: list = []
        self._async_guard_depth = 0

    # -- device-rootedness --------------------------------------------------

    def _rooted(self, node) -> bool:
        if isinstance(node, ast.Attribute):
            if (isinstance(node.value, ast.Name)
                    and node.value.id == "self"
                    and node.attr in self.device_attrs):
                return True
            return self._rooted(node.value)
        if isinstance(node, ast.Subscript):
            return self._rooted(node.value)
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self._rooted(e) for e in node.elts)
        return False

    def _collect_taint(self, fn: ast.FunctionDef):
        # two passes so a = jit(...); b = a chains resolve
        for _ in range(2):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign):
                    continue
                val = node.value
                from_jit = (isinstance(val, ast.Call)
                            and isinstance(val.func, ast.Attribute)
                            and val.func.attr.startswith("_jit_"))
                if not (from_jit or self._rooted(val)):
                    continue
                for tgt in node.targets:
                    for el in ([tgt] if not isinstance(tgt, ast.Tuple)
                               else tgt.elts):
                        if isinstance(el, ast.Name):
                            self.tainted.add(el.id)

    # -- violations ---------------------------------------------------------

    def _flag(self, node, what: str):
        self.findings.append(self.rule.finding(
            f"engine.{self.method}",
            f"{what} at line {node.lineno} — blocking device->host sync "
            "in the hot loop (only _harvest may read back)",
            method=self.method, line=node.lineno, what=what))

    def visit_Call(self, node: ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr == "item" and self._rooted(f.value):
                self._flag(node, ".item() on a device array")
            elif f.attr == "block_until_ready":
                if self._async_guard_depth == 0:
                    self._flag(node, ".block_until_ready() outside an "
                                     "async_steps guard")
            elif (isinstance(f.value, ast.Name) and f.value.id == "np"
                  and any(self._rooted(a) for a in node.args)):
                self._flag(node, f"np.{f.attr}() on a device array")
            elif (f.attr == "device_get"
                  and isinstance(f.value, ast.Name)
                  and f.value.id == "jax"):
                self._flag(node, "jax.device_get()")
        elif (isinstance(f, ast.Name) and f.id in _FORCING_BUILTINS
              and any(self._rooted(a) for a in node.args)):
            self._flag(node, f"{f.id}() on a device array")
        self.generic_visit(node)

    def _visit_test(self, node):
        if self._rooted(node.test):
            self._flag(node, "implicit bool() of a device array in a "
                             "branch test")

    def visit_If(self, node: ast.If):
        self._visit_test(node)
        guarded = "async_steps" in ast.dump(node.test)
        if guarded:
            self._async_guard_depth += 1
        for child in node.body:
            self.visit(child)
        if guarded:
            self._async_guard_depth -= 1
        for child in node.orelse:
            self.visit(child)
        self.visit(node.test)

    def visit_While(self, node: ast.While):
        self._visit_test(node)
        self.generic_visit(node)


class HostSyncRule(Rule):
    rule_id = "R4"
    name = "host-sync"
    description = ("no blocking device->host reads in hot-loop methods "
                   "outside harvest boundaries")
    requires = "source"

    def __init__(self, methods=HOT_METHODS, device_attrs=DEVICE_ATTRS):
        self.methods = methods
        self.device_attrs = device_attrs

    def check_source(self, source: str | None = None,
                     program: str = "serving/engine.py") -> list:
        tree = ast.parse(textwrap.dedent(source if source is not None
                                         else _engine_source()))
        findings = []
        for node in ast.walk(tree):
            if (isinstance(node, ast.FunctionDef)
                    and node.name in self.methods):
                scan = _MethodScan(self, node.name, self.device_attrs)
                scan.method = node.name
                scan._collect_taint(node)
                for stmt in node.body:
                    scan.visit(stmt)
                for f in scan.findings:
                    findings.append(f)
        return findings
