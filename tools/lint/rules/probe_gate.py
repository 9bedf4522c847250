"""R5 — probe-gate coverage: Pallas kernels reachable only behind their
support.py probe, with an XLA fallback sibling.

Whether Mosaic accepts a kernel's BlockSpecs is only knowable at compile
time on real hardware (the r3 postmortem), so every selection site must
ask ``ops/pallas/support.py`` first (``gate_attn_impl`` /
``kernel_error`` / ``kernel_or_warn`` / ``kernel_available``) and hold an XLA path to fall
back to.  This rule checks, statically, that serve code cannot reach a
kernel any other way:

1. The GATED KERNEL SET is parsed out of ``support.py``'s ``KERNELS``
   tuple — the lint can never drift from what the probes cover.
2. A gate-taint analysis over each serve module marks every name/
   attribute derived from a gate-function result (``err =
   kernel_or_warn(...)``, ``self.ragged_attn_impl`` assigned from ``err
   is None``), propagating through assignments,
   conditional branches, and call arguments into callee parameters.
3. Every reference to a gated kernel symbol must sit under a
   conditional whose test reads gate taint — either directly in its
   function, or (for builder methods) at every module-local call site.
4. The guarding conditional must have a live alternative (an ``else``,
   a ternary alternative, or fall-through statements): that alternative
   IS the XLA fallback sibling.
"""

from __future__ import annotations

import ast
import functools

from tools.lint.core import (
    REPO_ROOT,
    Finding,
    SourceFile,
    attr_chain,
    call_name,
    walk_within,
)

RULE_ID = "R5"

SUPPORT_PATH = "llm_np_cp_tpu/ops/pallas/support.py"
GATE_FUNCS = {"gate_attn_impl", "kernel_error", "kernel_or_warn",
              "kernel_available"}
PALLAS_PREFIX = "llm_np_cp_tpu.ops.pallas"
# symbols from ops/pallas that are NOT device kernels (metadata and the
# XLA fallbacks live in the same modules)
_FALLBACK_MARK = "_xla"


@functools.lru_cache(maxsize=1)
def gated_kernels() -> frozenset[str]:
    """Kernel callables gated by support.py probes, derived from its
    ``KERNELS`` tuple — the one list the probes, the bench ``kernels``
    child and the on-chip matrix iterate — so rule and probes cannot
    drift."""
    tree = ast.parse((REPO_ROOT / SUPPORT_PATH).read_text())
    names: set[str] = set()
    for node in tree.body:
        if not (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "KERNELS"
                        for t in node.targets)
                and isinstance(node.value, (ast.Tuple, ast.List))):
            continue
        names.update(
            c.value for c in node.value.elts
            if isinstance(c, ast.Constant) and isinstance(c.value, str)
        )
    # int8 probe variants share one callable with the base kernel
    return frozenset(
        n[: -len("_int8")] if n.endswith("_int8") else n for n in names
    )


def _gated_imports(sf: SourceFile) -> tuple[dict[str, str], set[str]]:
    """→ (kernel alias → kernel symbol, pallas MODULE aliases).

    Covers both spellings: ``from ...pallas.decode_attention import
    ragged_paged_attention [as x]`` binds the kernel directly, while
    ``from ...ops.pallas import decode_attention`` / ``import
    ...pallas.decode_attention as da`` bind a module whose attributes
    reach the kernels — both must be gate-checked."""
    kernels = gated_kernels()
    symbols: dict[str, str] = {}
    modules: set[str] = set()
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == PALLAS_PREFIX:
                # submodule imports (decode_attention is BOTH a module
                # and a kernel name — here it is the module)
                modules.update(a.asname or a.name for a in node.names)
            elif mod.startswith(PALLAS_PREFIX):
                for alias in node.names:
                    if alias.name in kernels:
                        symbols[alias.asname or alias.name] = alias.name
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith(PALLAS_PREFIX):
                    modules.add(alias.asname or alias.name.split(".")[-1])
    return symbols, modules


def _bound_names(fn: ast.AST) -> set[str]:
    """The names ``fn`` binds itself: its parameters and what its own
    body assigns (nested functions not followed)."""
    a = fn.args
    bound = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
    bound |= {p.arg for p in (a.vararg, a.kwarg) if p is not None}
    for node in walk_within(fn, skip_nested=True):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            bound.add(node.id)
    return bound


class _Taint:
    """Module-wide gate-taint: tainted locals per function, tainted
    ``self.<attr>`` names per module, computed to a fixed point."""

    def __init__(self, sf: SourceFile) -> None:
        self.sf = sf
        self.attrs: set[str] = set()
        self.local: dict[ast.AST, set[str]] = {}
        funcs = [fn for _, fn in sf.iter_functions()]
        for fn in funcs:
            self.local[fn] = set()
        for _ in range(4):  # small fixed-point ladder
            before = (len(self.attrs),
                      sum(len(v) for v in self.local.values()))
            for fn in funcs:
                self._scan_function(fn)
            self._propagate_params(funcs)
            after = (len(self.attrs),
                     sum(len(v) for v in self.local.values()))
            if after == before:
                break

    def expr_tainted(self, node: ast.AST, fn: ast.AST) -> bool:
        # a nested function reads its builder's locals as closure
        # variables (``use_kernel`` inside the jitted step) — but not a
        # name an inner function binds itself: that one shadows it
        names = set(self.local.get(fn, ()))
        shadowed = _bound_names(fn)
        for anc in self.sf.ancestors(fn):
            if anc in self.local:
                names |= self.local[anc] - shadowed
                shadowed |= _bound_names(anc)
        for n in ast.walk(node):
            if isinstance(n, ast.Name) and n.id in names:
                return True
            if isinstance(n, ast.Name) and n.id in GATE_FUNCS:
                return True
            if isinstance(n, ast.Attribute) and n.attr in (
                self.attrs | GATE_FUNCS
            ):
                return True
        return False

    def _branch_tainted(self, node: ast.AST, fn: ast.AST) -> bool:
        """Is this statement under an if/ternary testing gate taint?"""
        for anc in self.sf.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
            if isinstance(anc, (ast.If, ast.IfExp, ast.While)) \
                    and self.expr_tainted(anc.test, fn):
                return True
        return False

    def _scan_function(self, fn: ast.AST) -> None:
        names = self.local[fn]
        for node in walk_within(fn, skip_nested=True):
            if not isinstance(node, (ast.Assign, ast.AugAssign)):
                continue
            tainted = self.expr_tainted(node.value, fn) \
                or self._branch_tainted(node, fn)
            if not tainted:
                continue
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                    else [t]
                for el in elts:
                    if isinstance(el, ast.Name):
                        names.add(el.id)
                    else:
                        chain = attr_chain(el)
                        if chain and chain[0] == "self":
                            self.attrs.add(chain[-1])

    def _propagate_params(self, funcs: list) -> None:
        by_name: dict[str, list[ast.AST]] = {}
        for fn in funcs:
            by_name.setdefault(fn.name, []).append(fn)
        for fn in funcs:
            for node in walk_within(fn, skip_nested=True):
                if not isinstance(node, ast.Call):
                    continue
                chain = call_name(node)
                if not chain:
                    continue
                callee_name = chain[-1]
                for callee in by_name.get(callee_name, ()):
                    params = [a.arg for a in callee.args.args]
                    if params and params[0] == "self":
                        params = params[1:]
                    for i, arg in enumerate(node.args):
                        if i < len(params) \
                                and self.expr_tainted(arg, fn):
                            self.local[callee].add(params[i])
                    for kw in node.keywords:
                        if kw.arg in params \
                                and self.expr_tainted(kw.value, fn):
                            self.local[callee].add(kw.arg)


def _has_alternative(sf: SourceFile, guard: ast.AST,
                     symbol_key: str) -> bool:
    """Does the guarding conditional carry a live non-kernel branch?"""

    def refs_symbol(n: ast.AST) -> bool:
        return any(
            (isinstance(x, ast.Name) and x.id == symbol_key)
            or (isinstance(x, ast.Attribute) and x.attr == symbol_key)
            for x in ast.walk(n)
        )

    if isinstance(guard, ast.IfExp):
        return not refs_symbol(guard.orelse)
    if isinstance(guard, ast.If):
        if guard.orelse and not any(refs_symbol(n) for n in guard.orelse):
            return True
        parent = sf.parents.get(guard)
        body = getattr(parent, "body", None)
        if isinstance(body, list) and guard in body:
            after = body[body.index(guard) + 1:]
            return bool(after)
    return False


class _Rule:
    id = RULE_ID
    name = "probe-gate"
    targets = ("llm_np_cp_tpu/serve/**/*.py",)

    def check(self, sf: SourceFile) -> list[Finding]:
        findings = self._check_inner(sf)
        # the builder-pattern branch re-walks call sites once per alias
        # load — dedupe identical verdicts
        seen: set[tuple] = set()
        out = []
        for f in findings:
            key = (f.line, f.message)
            if key not in seen:
                seen.add(key)
                out.append(f)
        return out

    def _check_inner(self, sf: SourceFile) -> list[Finding]:
        aliases, mod_aliases = _gated_imports(sf)
        if not aliases and not mod_aliases:
            return []
        kernels = gated_kernels()
        taint = _Taint(sf)
        out: list[Finding] = []
        # call sites per function name, for builder-level gating
        calls_of: dict[str, list[tuple[ast.AST, ast.Call]]] = {}
        for _, fn in sf.iter_functions():
            for node in walk_within(fn, skip_nested=True):
                if isinstance(node, ast.Call):
                    chain = call_name(node)
                    if chain:
                        calls_of.setdefault(chain[-1], []).append(
                            (fn, node)
                        )
        # kernel uses: direct symbol aliases, plus attribute access
        # through an imported pallas module (``decode_attention.
        # ragged_paged_attention(...)`` must not bypass the rule)
        uses: list[tuple[ast.AST, str, str]] = []
        for node in ast.walk(sf.tree):
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in aliases):
                uses.append((node, aliases[node.id], node.id))
            elif (isinstance(node, ast.Attribute)
                  and node.attr in kernels):
                chain = attr_chain(node.value)
                if chain and (chain[-1] in mod_aliases
                              or "pallas" in chain):
                    uses.append((node, node.attr, node.attr))
        for node, kernel, key in uses:
            fn = sf.enclosing_function(node)
            if fn is None:
                continue
            guard = self._guard_of(sf, taint, node, fn)
            if guard is not None:
                if not _has_alternative(sf, guard, key):
                    out.append(Finding(
                        rule=self.id, path=sf.rel, line=node.lineno,
                        message=(
                            f"Pallas kernel {kernel!r} is "
                            "probe-gated but its conditional has no XLA "
                            "fallback sibling — a failed probe must "
                            "select a working path, not dead-end"
                        ),
                    ))
                continue
            # builder pattern: every module-local call site of the
            # top-level enclosing function must be probe-gated
            top = fn
            for anc in sf.ancestors(fn):
                if isinstance(anc, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    top = anc
            sites = calls_of.get(top.name, [])
            gated_sites = [
                (cfn, c) for cfn, c in sites
                if self._guard_of(sf, taint, c, cfn) is not None
            ]
            if sites and len(gated_sites) == len(sites):
                for cfn, c in sites:
                    g = self._guard_of(sf, taint, c, cfn)
                    if not _has_alternative(sf, g, top.name):
                        out.append(Finding(
                            rule=self.id, path=sf.rel, line=c.lineno,
                            message=(
                                f"probe-gated call into {top.name}() "
                                f"(reaches Pallas kernel "
                                f"{kernel!r}) has no XLA "
                                "fallback sibling"
                            ),
                        ))
                continue
            out.append(Finding(
                rule=self.id, path=sf.rel, line=node.lineno,
                message=(
                    f"Pallas kernel {kernel!r} reachable "
                    "without its support.py probe gate — select it only "
                    "behind gate_attn_impl/kernel_error with an XLA "
                    "fallback (a Mosaic reject must degrade, not crash)"
                ),
            ))
        return out

    @staticmethod
    def _guard_of(sf: SourceFile, taint: _Taint, node: ast.AST,
                  fn: ast.AST) -> ast.AST | None:
        """Nearest enclosing conditional whose test reads gate taint."""
        for anc in sf.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return None
            if isinstance(anc, (ast.If, ast.IfExp, ast.While)) \
                    and taint.expr_tainted(anc.test, fn):
                return anc
        return None
    # note: _FALLBACK_MARK documents the naming convention for XLA
    # fallback siblings (e.g. ragged_paged_attention_xla); the
    # alternative-branch check above is what enforces their presence


RULE = _Rule()
