"""R2 — host-sync: device→host transfers outside the designated
``host_sync``/``deliver`` phase bodies of the engine tick.

A tick is one async dispatch plus host bookkeeping; any early sync
(``.item()``, ``np.asarray`` on a dispatch result, ``jax.device_get``,
``block_until_ready``) serializes the host against the device mid-tick
and shows up as dead time in the phase trace (the PR-5 finding this rule
pins).  The tick's phase structure is recovered from the code itself:
a "tick method" is one that calls ``self.tracer.tick(t0, ((name, ta,
tb), ...))``, and each phase's span is the statements between the last
assignments to its start/end timestamp variables — so the rule follows
the same phase boundaries the trace reports, with no shadow table to
drift.

Scope: tick methods plus every ``self._helper()`` they (transitively)
call from a NON-exempt phase.  Tick methods are recovered from the
``tracer.tick`` call, PLUS the qualnames in ``FLEET_TICK_METHODS`` —
the replica fleet tick (``ReplicaSet.step``) emits no phase slices, so
NOTHING in it is exempt: the fleet loop drives N engines' ticks
back-to-back, and a host sync there stalls every replica at once.
Within that scope:

- ``.item()``, ``jax.device_get(...)``, ``.block_until_ready()`` —
  flagged unconditionally.
- ``np.asarray(x)`` / ``np.array(x)`` / ``float(x)`` / ``int(x)`` —
  flagged only when ``x`` mentions a DEVICE-ORIGIN name: a local
  assigned from a jitted-step/dispatch call (``self._dispatch_*``,
  ``self._mixed_step``).  Host-side numpy packing stays legal.

ONE-FETCH TIGHTENING (the tick-tail fusion contract): the exempt
``host_sync``/``deliver`` spans are no longer a free-fire zone — the
step returns ONE packed int32 sync array (token, finished, watermark,
accept), so a tick method gets exactly ONE device sync across its
exempt spans (the designated packed fetch).  Any second sync there —
the scattered ``np.asarray`` sites this rule's tightening retired —
bites with its own message.  Reads of the ALREADY-FETCHED host array
(``int(out_host[...])``) are host-side and stay legal.
"""

from __future__ import annotations

import ast
import re

from tools.lint.core import (
    Finding,
    SourceFile,
    assigned_names,
    attr_chain,
    call_name,
    walk_within,
)

RULE_ID = "R2"

EXEMPT_PHASES = {"host_sync", "deliver"}
# fleet-tick methods scanned WITHOUT any exempt phase spans (no
# tracer.tick call to recover them from), matched by qualname so the
# bite fixture's fake ReplicaSet exercises the same path
FLEET_TICK_METHODS = ("ReplicaSet.step",)
# engine attributes whose call results live on device
_DEVICE_CALL_RE = re.compile(
    r"^_(dispatch_\w+|mixed_step)$"
)
_NP_SYNC = {("np", "asarray"), ("np", "array"), ("numpy", "asarray"),
            ("numpy", "array")}
_CAST_SYNC = {("float",), ("int",), ("bool",)}


def _tick_phase_tuple(fn: ast.AST) -> ast.Tuple | None:
    """The ``((name, ta, tb), ...)`` tuple of a ``*.tracer.tick`` call
    in this function, or None."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        chain = call_name(node)
        if chain and chain[-1] == "tick" and "tracer" in chain[:-1]:
            for arg in node.args[1:2]:
                if isinstance(arg, ast.Tuple):
                    return arg
    return None


def _exempt_spans(fn: ast.AST, phases: ast.Tuple) -> list[tuple[int, int]]:
    """Line spans (a, b] of the exempt phases: a phase owns the
    statements between the LAST assignment to its start timestamp and
    the last assignment to its end timestamp."""
    last_assign: dict[str, int] = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                for name in assigned_names(t):
                    last_assign[name] = max(
                        last_assign.get(name, 0), node.lineno
                    )
    spans: list[tuple[int, int]] = []
    for elt in phases.elts:
        if not (isinstance(elt, ast.Tuple) and len(elt.elts) == 3):
            continue
        name_n, ta, tb = elt.elts
        if not (isinstance(name_n, ast.Constant)
                and name_n.value in EXEMPT_PHASES):
            continue
        if isinstance(ta, ast.Name) and isinstance(tb, ast.Name):
            a = last_assign.get(ta.id)
            b = last_assign.get(tb.id)
            if a is not None and b is not None and b > a:
                spans.append((a, b))
    return spans


def _device_names(fn: ast.AST) -> set[str]:
    """Locals assigned from device-returning engine calls."""
    out: set[str] = set()
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        chain = call_name(node.value)
        if not chain or not _DEVICE_CALL_RE.match(chain[-1]):
            continue
        for t in node.targets:
            out.update(assigned_names(t))
    return out


def _mentions(node: ast.AST, names: set[str]) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id in names for n in ast.walk(node)
    )


class _Rule:
    id = RULE_ID
    name = "host-sync"
    targets = ("llm_np_cp_tpu/serve/engine.py",
               "llm_np_cp_tpu/serve/replica.py")

    def check(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        for cls in ast.walk(sf.tree):
            if isinstance(cls, ast.ClassDef):
                self._check_class(sf, cls, out)
        return out

    def _check_class(self, sf: SourceFile, cls: ast.ClassDef,
                     out: list[Finding]) -> None:
        methods = {
            n.name: n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        ticks = {
            name: tup for name, fn in methods.items()
            if (tup := _tick_phase_tuple(fn)) is not None
        }
        for name in methods:
            if (f"{cls.name}.{name}" in FLEET_TICK_METHODS
                    and name not in ticks):
                ticks[name] = None  # fleet tick: no exempt spans at all
        if not ticks:
            return
        # helper closure reached from non-exempt tick positions
        exempt: dict[str, list[tuple[int, int]]] = {
            name: (_exempt_spans(methods[name], tup)
                   if tup is not None else [])
            for name, tup in ticks.items()
        }

        def in_exempt(name: str, lineno: int) -> bool:
            return any(a < lineno <= b for a, b in exempt.get(name, ()))

        reach: set[str] = set()
        frontier = list(ticks)
        while frontier:
            fname = frontier.pop()
            fn = methods[fname]
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                chain = call_name(node)
                if (
                    chain and len(chain) == 2 and chain[0] == "self"
                    and chain[1] in methods
                    and chain[1] not in ticks
                    and chain[1] not in reach
                    and not (fname in ticks
                             and in_exempt(fname, node.lineno))
                ):
                    reach.add(chain[1])
                    frontier.append(chain[1])

        for fname in list(ticks) + sorted(reach):
            fn = methods[fname]
            device = _device_names(fn)
            calls = sorted(
                (n for n in walk_within(fn) if isinstance(n, ast.Call)),
                key=lambda n: (n.lineno, n.col_offset),
            )
            # the ONE designated packed fetch per tick method: the
            # first sync inside the exempt spans is the contract; every
            # further sync there bites (the scattered-asarray class)
            fetch_seen = False
            for node in calls:
                line = node.lineno
                chain = call_name(node)
                msg = None
                if chain and chain[-1] == "item" and len(chain) > 1:
                    msg = ".item() forces a device→host sync"
                elif chain and chain[-2:] == ("jax", "device_get"):
                    msg = "jax.device_get() forces a device→host sync"
                elif chain and chain[-1] == "block_until_ready":
                    msg = ".block_until_ready() blocks the tick thread"
                elif chain in _NP_SYNC or chain in _CAST_SYNC:
                    if node.args and _mentions(node.args[0], device):
                        what = ".".join(chain)
                        msg = (
                            f"{what}() on a dispatch result "
                            f"({', '.join(sorted(device & {n.id for n in ast.walk(node.args[0]) if isinstance(n, ast.Name)}))}) "
                            "syncs device→host"
                        )
                if msg is None:
                    continue
                if fname in ticks and in_exempt(fname, line):
                    if not fetch_seen:
                        fetch_seen = True  # the designated packed fetch
                        continue
                    out.append(Finding(
                        rule=self.id, path=sf.rel, line=line,
                        message=(
                            f"{msg} inside {fname}()'s host_sync/"
                            "deliver phases, AFTER the tick's "
                            "designated fetch — the one-fetch contract "
                            "packs everything the host needs into ONE "
                            "int32 transfer; fold this into the packed "
                            "sync array instead"
                        ),
                    ))
                    continue
                out.append(Finding(
                    rule=self.id, path=sf.rel, line=line,
                    message=(
                        f"{msg} inside tick path {fname}() outside "
                        "the designated host_sync/deliver phase — "
                        "move it into host_sync, or batch it with "
                        "the tick's one fetch"
                    ),
                ))


RULE = _Rule()
