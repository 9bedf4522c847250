"""R7 — donation-discipline: donated buffers must not be reused after a
faulted dispatch.

The engine's jitted steps DONATE the pool pages (``donate_argnums``):
the XLA program takes ownership of the buffer and the caller's handle is
deleted once the dispatch consumes it.  The runtime-degradation retries
(``_dispatch_decode`` / ``_dispatch_mixed``) re-call the step with the
SAME ``self.pool.pages`` expression inside the ``except`` handler — if
the fault struck AFTER the donated buffer was consumed, the retry raises
on deleted buffers (or worse, on a backend that zero-copies, reads
garbage).  That caveat has lived in a comment since PR 4; this rule pins
it at source so every future retry site has to either rebuild the
donated operand or carry a reasoned suppression explaining why the reuse
is safe (the engine's two sites are safe because injected faults fire
BEFORE dispatch and a real post-donation fault escalates to the
supervisor's pool rebuild).

Mechanics (no shadow table — the donating set is parsed from the code):

- a *donating step* is an inner function decorated
  ``@partial(jax.jit, donate_argnums=(...))`` (or ``jax.jit(...,
  donate_argnums=...)``) inside a ``_make_*`` builder method; the
  engine attribute it lands on is recovered from ``self.X =
  self._make_Y(...)`` assignments (builders that return another
  builder's result chain transitively);
- a finding is a call to a donating attribute inside an ``except``
  handler whose TRY body also calls it, passing a textually identical
  expression at a donated argument position — the donated operand was
  not rebuilt between the fault and the retry.
"""

from __future__ import annotations

import ast

from tools.lint.core import Finding, SourceFile, call_name

RULE_ID = "R7"


def _donate_positions(fn: ast.AST) -> set[int]:
    """Donated argument indices from a ``partial(jax.jit,
    donate_argnums=...)`` / ``jax.jit(..., donate_argnums=...)``
    decorator on ``fn`` (literal tuples/ints only)."""
    out: set[int] = set()
    for dec in getattr(fn, "decorator_list", ()):
        if not isinstance(dec, ast.Call):
            continue
        for kw in dec.keywords:
            if kw.arg != "donate_argnums":
                continue
            val = kw.value
            elts = val.elts if isinstance(val, (ast.Tuple, ast.List)) \
                else [val]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.add(e.value)
    return out


def _maker_donations(cls: ast.ClassDef) -> dict[str, set[int]]:
    """``_make_*`` method name → donated positions of any donating inner
    step it builds, chained through makers that return another maker's
    result."""
    makers: dict[str, set[int]] = {}
    calls: dict[str, set[str]] = {}
    methods = {
        n.name: n for n in cls.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    for name, fn in methods.items():
        if not name.startswith("_make"):
            continue
        donated: set[int] = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                donated |= _donate_positions(node)
        makers[name] = donated
        calls[name] = {
            chain[1] for node in ast.walk(fn)
            if isinstance(node, ast.Call)
            and (chain := call_name(node)) is not None
            and len(chain) == 2 and chain[0] == "self"
            and chain[1].startswith("_make")
        }
    changed = True
    while changed:  # propagate through maker→maker chains
        changed = False
        for name, callees in calls.items():
            for callee in callees:
                extra = makers.get(callee, set()) - makers[name]
                if extra:
                    makers[name] |= extra
                    changed = True
    return makers


def _donating_attrs(cls: ast.ClassDef) -> dict[str, set[int]]:
    """Engine attribute → donated call-site argument positions, from
    ``self.X = self._make_Y(...)`` assignments."""
    makers = _maker_donations(cls)
    out: dict[str, set[int]] = {}
    for node in ast.walk(cls):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        chain = call_name(node.value)
        if not (chain and len(chain) == 2 and chain[0] == "self"):
            continue
        donated = makers.get(chain[1])
        if not donated:
            continue
        for t in node.targets:
            if (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                out.setdefault(t.attr, set()).update(donated)
    return out


def _donated_args(call: ast.Call, positions: set[int]) -> dict[int, str]:
    """Donated-position argument dumps, positions past a ``*args`` star
    excluded (their alignment is unknowable statically)."""
    out: dict[int, str] = {}
    for i, arg in enumerate(call.args):
        if isinstance(arg, ast.Starred):
            break
        if i in positions:
            out[i] = ast.dump(arg)
    return out


class _Rule:
    id = RULE_ID
    name = "donation-discipline"
    targets = ("llm_np_cp_tpu/serve/engine.py",)

    def check(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        for cls in ast.walk(sf.tree):
            if isinstance(cls, ast.ClassDef):
                self._check_class(sf, cls, out)
        return out

    def _check_class(self, sf: SourceFile, cls: ast.ClassDef,
                     out: list[Finding]) -> None:
        donating = _donating_attrs(cls)
        if not donating:
            return
        for node in ast.walk(cls):
            if not isinstance(node, ast.Try):
                continue
            # donating calls in the try body (handlers excluded — their
            # own nested tries are walked separately)
            tried: dict[str, dict[int, str]] = {}
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if not isinstance(sub, ast.Call):
                        continue
                    chain = call_name(sub)
                    if (chain and len(chain) == 2 and chain[0] == "self"
                            and chain[1] in donating):
                        tried.setdefault(chain[1], {}).update(
                            _donated_args(sub, donating[chain[1]])
                        )
            if not tried:
                continue
            for handler in node.handlers:
                for sub in ast.walk(handler):
                    if not isinstance(sub, ast.Call):
                        continue
                    chain = call_name(sub)
                    if not (chain and len(chain) == 2
                            and chain[0] == "self" and chain[1] in tried):
                        continue
                    retry = _donated_args(sub, donating[chain[1]])
                    shared = [
                        i for i, dump in retry.items()
                        if tried[chain[1]].get(i) == dump
                    ]
                    if shared:
                        out.append(Finding(
                            rule=self.id, path=sf.rel, line=sub.lineno,
                            message=(
                                f"self.{chain[1]}() retried in an "
                                "except handler with the same donated "
                                f"operand (arg {shared[0]}) the faulted "
                                "dispatch may have consumed — rebuild "
                                "the donated buffer before retrying, or "
                                "explain why the reuse is safe"
                            ),
                        ))


RULE = _Rule()
