"""R4 — guarded-hook discipline for the optional hot-path hooks.

The serve stack's optional instruments — the ``tracer``
(serve/tracing.TraceRecorder), the ``faults`` chaos injector
(serve/faults.FaultInjector), the ``journal`` durable request journal
(serve/journal.RequestJournal), the ``request_log`` canonical request
log (serve/request_log.RequestLog), the ``sentinel`` tick anomaly
detector, the ``slo`` goodput tracker (serve/slo.py), the
``actions`` lifecycle auto-action policy (serve/lifecycle.py), the
``telemetry`` device roofline model (serve/telemetry.TelemetryModel),
the ``otel`` OTLP span sink (serve/otel.OtlpExporter, hung off the
TraceRecorder), the ``host_tier`` host-RAM KV block tier
(serve/host_tier.HostTier) and the ``tenants`` multi-tenant ledger
(serve/tenants.TenantLedger) — are OFF by
default, spelled as ``None`` attributes.  The zero-overhead contract is that every hook call sits
behind an ``is None`` / ``is not None`` check in the same function, so
instruments-off costs an attribute load and a branch: no dict built for
a recorder that is not there, no allocation the hot loop did not make
before instrumentation existed.

This generalizes (and absorbs — see the back-compat shim in
tools/compile_counter.py) the original ``assert_tracing_hooks_guarded``
AST check: it now covers the FaultInjector AND the tracer across every
serve hot-path module, not just two files.

Second check: what exists ONLY for the tracer — the unified tick's
phase marks (``self._phase_mark``, which switches the ``serve.<phase>``
profiler annotation), ``jax.profiler.TraceAnnotation`` scopes, and the
tick thread's CPU clock (``time.thread_time_ns``) — must sit in the
taken branch of a ``tracer is not None`` test (an ``if`` body or the
true arm of a conditional expression), so tracing-off pays no
timestamp, allocation or call for them.

Third check, engine-only: the supervisor mutes a zombie engine by
REPLACING ``self.metrics`` / clearing ``self.tracer`` — so engine tick
code must re-read those attributes at every hook and never cache them
in a local for the tick (a cached binding would keep a superseded hung
tick writing into the metrics/timeline the rebuilt engine now owns).
"""

from __future__ import annotations

import ast
import pathlib

from tools.lint.core import (
    REPO_ROOT,
    Finding,
    SourceFile,
    attr_chain,
    walk_within,
)

RULE_ID = "R4"

HOOKS = ("tracer", "faults", "journal", "request_log", "sentinel", "slo",
         "actions", "telemetry", "otel", "host_tier", "tenants")
# engine methods where binding self.tracer/self.metrics/self.journal to
# a local is fine: construction, cloning, and the warmup
# suspend/restore swap — none of them run inside a supervised tick
_CACHE_EXEMPT = {"__init__", "clone_fresh", "warmup", "_warmup_body",
                 "replay_trace"}


def scan_hook_guards(
    tree: ast.AST, rel: str, hooks: tuple[str, ...] = HOOKS,
) -> list[tuple[int, str]]:
    """→ ``[(lineno, message)]`` for unguarded hook calls.  The message
    text keeps the original lint's phrasing (tests match on it)."""
    problems: list[tuple[int, str]] = []
    seen: set[str] = set()
    for fn in (n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))):
        for hook in hooks:
            hook_locals: set[str] = set()
            attr_guarded = False
            name_guarded: set[str] = set()
            # full walk, nested defs included: a guard established in
            # the enclosing function covers its closures (the original
            # assert_tracing_hooks_guarded semantics, kept bit-for-bit
            # for the back-compat shim)
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign):
                    v = node.value
                    is_hook = (
                        isinstance(v, ast.Attribute) and v.attr == hook
                    ) or (
                        isinstance(v, ast.Call)
                        and isinstance(v.func, ast.Name)
                        and v.func.id == "getattr"
                        and len(v.args) >= 2
                        and isinstance(v.args[1], ast.Constant)
                        and v.args[1].value == hook
                    )
                    if is_hook:
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                hook_locals.add(t.id)
                elif isinstance(node, ast.Compare) and any(
                    isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
                ) and any(
                    isinstance(c, ast.Constant) and c.value is None
                    for c in node.comparators
                ):
                    if isinstance(node.left, ast.Name):
                        name_guarded.add(node.left.id)
                    elif (isinstance(node.left, ast.Attribute)
                          and node.left.attr == hook):
                        attr_guarded = True
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)):
                    continue
                base = node.func.value
                msg = None
                if isinstance(base, ast.Attribute) and base.attr == hook:
                    if not attr_guarded:
                        msg = (
                            f"{rel}:{node.lineno}: .{hook}."
                            f"{node.func.attr}() in {fn.name}() without "
                            f"an 'is (not) None' guard on the {hook} "
                            "attribute"
                        )
                elif (isinstance(base, ast.Name)
                      and base.id in hook_locals
                      and base.id not in name_guarded):
                    msg = (
                        f"{rel}:{node.lineno}: {hook} local "
                        f"{base.id!r} called in {fn.name}() without an "
                        "'is (not) None' guard"
                    )
                if msg is not None and msg not in seen:
                    seen.add(msg)
                    problems.append((node.lineno, msg))
    return problems


# callee chains that exist only for the tracer (second check)
TRACING_ONLY_CALLS = (
    ("self", "_phase_mark"),
    ("time", "thread_time_ns"),
    ("jax", "profiler", "TraceAnnotation"),
)
# the helper's own body: every caller holds the guard
_TRACING_ONLY_EXEMPT = {"_phase_mark"}


def _is_tracer_test(test: ast.AST) -> bool:
    """``<...>.tracer is not None`` / ``tracer is not None``, alone or
    as a conjunct of an ``and``."""
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        return any(_is_tracer_test(v) for v in test.values)
    if not (isinstance(test, ast.Compare) and len(test.ops) == 1
            and isinstance(test.ops[0], ast.IsNot)
            and isinstance(test.comparators[0], ast.Constant)
            and test.comparators[0].value is None):
        return False
    left = test.left
    return (isinstance(left, ast.Attribute) and left.attr == "tracer") or (
        isinstance(left, ast.Name) and left.id in ("tracer", "tr"))


def scan_tracing_only_calls(tree: ast.AST, rel: str) -> list[tuple[int, str]]:
    """→ ``[(lineno, message)]`` for tracing-only calls outside the
    taken branch of a tracer-is-not-None test."""
    problems: list[tuple[int, str]] = []

    def visit(node: ast.AST, guarded: bool, fn_name: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            guarded, fn_name = False, node.name
        if isinstance(node, ast.Call) and not guarded \
                and fn_name not in _TRACING_ONLY_EXEMPT:
            chain = attr_chain(node.func)
            if chain in TRACING_ONLY_CALLS:
                problems.append((node.lineno, (
                    f"{rel}:{node.lineno}: {'.'.join(chain)}() in "
                    f"{fn_name}() outside a 'tracer is not None' branch "
                    "— tracing-only work must cost nothing when off"
                )))
        if isinstance(node, (ast.If, ast.IfExp)) \
                and _is_tracer_test(node.test):
            taken = node.body if isinstance(node.body, list) else [node.body]
            other = (node.orelse if isinstance(node.orelse, list)
                     else [node.orelse])
            for child in taken:
                visit(child, True, fn_name)
            for child in [node.test, *other]:
                visit(child, guarded, fn_name)
            return
        for child in ast.iter_child_nodes(node):
            visit(child, guarded, fn_name)

    visit(tree, False, "<module>")
    return problems


def scan_hook_guard_files(
    files: tuple[str, ...], hooks: tuple[str, ...] = ("tracer",),
) -> list[str]:
    """Back-compat surface for tools/compile_counter.py's
    ``assert_tracing_hooks_guarded`` shim: scan paths (repo-relative or
    absolute) and return the formatted problem strings."""
    out: list[str] = []
    for rel in files:
        path = pathlib.Path(rel)
        if not path.is_absolute():
            path = REPO_ROOT / rel
        tree = ast.parse(path.read_text())
        out.extend(msg for _, msg in scan_hook_guards(tree, str(rel), hooks))
        if "tracer" in hooks:
            out.extend(
                msg for _, msg in scan_tracing_only_calls(tree, str(rel)))
    return out


class _Rule:
    id = RULE_ID
    name = "guarded-hook"
    targets = ("llm_np_cp_tpu/serve/**/*.py",)

    def check(self, sf: SourceFile) -> list[Finding]:
        out = [
            Finding(rule=self.id, path=sf.rel, line=line,
                    message=msg.split(": ", 1)[1])
            for line, msg in (scan_hook_guards(sf.tree, sf.rel)
                              + scan_tracing_only_calls(sf.tree, sf.rel))
        ]
        if sf.rel.endswith("serve/engine.py"):
            self._check_no_cache(sf, out)
        return out

    def _check_no_cache(self, sf: SourceFile, out: list[Finding]) -> None:
        for qualname, fn in sf.iter_functions():
            name = qualname.rsplit(".", 1)[-1]
            if name in _CACHE_EXEMPT:
                continue
            for node in walk_within(fn, skip_nested=True):
                if not isinstance(node, ast.Assign):
                    continue
                chain = attr_chain(node.value)
                if chain is None or len(chain) != 2 or chain[0] != "self":
                    continue
                if chain[1] not in ("tracer", "metrics", "journal",
                                    "request_log", "actions",
                                    "telemetry", "host_tier", "tenants"):
                    continue
                if not any(isinstance(t, ast.Name) for t in node.targets):
                    continue
                out.append(Finding(
                    rule=self.id, path=sf.rel, line=node.lineno,
                    message=(
                        f"self.{chain[1]} cached in a local in "
                        f"{qualname}() — the supervisor mutes zombie "
                        "engines by swapping this attribute, so tick "
                        "code must re-read it at every hook"
                    ),
                ))


RULE = _Rule()
