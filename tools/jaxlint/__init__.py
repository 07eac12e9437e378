"""jaxlint: repo-specific static analysis for the jit/shape/distance contracts.

    python tools/jaxlint [--root .] [paths...]

Stdlib-only (the ``tools/check_docs.py`` dependency discipline).  Every
rule is traceable to a shipped bug or contract; the catalog with the
originating bug per rule lives in ``docs/static-analysis.md``:

  JL001  recompile hazards — unhashable values bound to ``static_argnames``
         (jit raises late, at dispatch) and host-built arrays
         (``jax.device_put`` / ``jnp.zeros``-family attribute state) in
         ``shard_map`` modules, the PR 9 dispatch-cache-split class.
  JL002  fixed-shape violations in ``src/repro/core`` + ``src/repro/kernels``
         — ``jnp.nonzero`` / ``jnp.flatnonzero`` / ``jnp.unique`` without
         ``size=``, one-arg ``jnp.where``, boolean-mask indexing and
         data-dependent ``reshape``: all trace-time shape landmines.
  JL003  host sync inside a device loop — ``.item()``, ``np.asarray`` /
         ``np.array``, ``jax.device_get``, ``block_until_ready``,
         ``float()``/``int()`` over ``jnp`` expressions in a ``for``/
         ``while`` body.  Functions that time themselves (any
         ``time.perf_counter`` / ``time.time`` / ``time.monotonic`` call)
         are treated as timed regions and exempt — measurement loops in
         ``serve.py`` and the benchmarks sync on purpose.
  JL004  distance-contract completeness — a class implementing part of the
         ``PairDistance`` batched-method set must implement all of it, and
         every literal policy kind in ``POLICY_KINDS`` must be handled
         inside ``DistancePolicy``.
  JL005  weak-type scalars reaching jitted signatures — bare Python
         numeric literals passed to a name bound by ``jax.jit`` (the other
         silent cache-splitter: ``f(0.5)`` and ``f(x)`` compile separately
         and weak-type promotion can flip result dtypes).

Findings are suppressed inline with ``# jaxlint: disable=JL00X[,JL00Y]``
(same line, or a standalone comment on the line above) — a bare
``disable`` without rule ids is invalid and ignored.  Pre-existing debt
lives in a committed baseline (``tools/jaxlint/baseline.json``), keyed by
line-insensitive fingerprints so unrelated edits don't invalidate it;
``--update-baseline`` rewrites it.  Exit 1 iff there are findings that are
neither suppressed nor baselined.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import hashlib
import json
import pathlib
import re
import sys
from typing import Iterable, Optional

RULES = {
    "JL001": "recompile hazard (unhashable static arg / host-built shard_map state)",
    "JL002": "fixed-shape violation (data-dependent shape in core/kernels)",
    "JL003": "host sync inside device loop (outside a timed region)",
    "JL004": "distance contract incomplete (PairDistance / DistancePolicy)",
    "JL005": "weak-type Python scalar reaching a jitted signature",
}

# the full batched-forms contract every PairDistance implementation carries
# (distances.Distance is the reference implementation); defining >= 2 of the
# repo-specific marker subset marks a class as a PairDistance implementation.
PAIR_DISTANCE_METHODS = frozenset({
    "matrix", "query_matrix", "pairwise", "pairwise_batch",
    "prep_scan", "prep_query", "score",
})
PAIR_DISTANCE_MARKERS = frozenset({
    "prep_scan", "prep_query", "pairwise_batch", "query_matrix",
})

# jnp constructors that build arrays host-side when called outside jit
HOST_ARRAY_CTORS = frozenset({
    "zeros", "ones", "full", "empty", "asarray", "array", "arange",
    "linspace", "zeros_like", "ones_like", "full_like",
})

DEFAULT_TARGETS = ("src", "benchmarks")
JL002_SCOPE = ("src/repro/core", "src/repro/kernels")

SUPPRESS_RE = re.compile(r"#\s*jaxlint:\s*disable=([A-Z0-9, ]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str  # repo-relative, posix
    line: int
    col: int
    rule: str
    message: str
    snippet: str

    def fingerprint(self, occurrence: int) -> str:
        """Line-insensitive identity: file + rule + code text + ordinal."""
        key = f"{self.path}|{self.rule}|{self.snippet}|{occurrence}"
        return hashlib.sha1(key.encode()).hexdigest()[:16]

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


# ---------------------------------------------------------------------------
# per-file analysis

def _dotted(node: ast.AST) -> Optional[str]:
    """``jax.numpy.zeros`` -> "jax.numpy.zeros" for Name/Attribute chains."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _const_strings(node: ast.AST) -> list[str]:
    return [n.value for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)]


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


class _FileLint:
    def __init__(self, path: pathlib.Path, rel: str, source: str,
                 in_jl002_scope: bool):
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.in_jl002_scope = in_jl002_scope
        self.tree = ast.parse(source, filename=str(path))
        self.findings: list[Finding] = []
        # alias -> canonical module for the modules the rules care about
        self.aliases: dict[str, str] = {}
        # names / attribute chains bound to jax.jit(...) results, plus
        # @jax.jit / @partial(jax.jit, ...) decorated defs
        self.jitted_names: set[str] = set()
        # jitted name -> static param names, for the wrapped-def lookup
        self.static_params: dict[str, set[str]] = {}
        self.defs: dict[str, ast.FunctionDef] = {}
        self.uses_shard_map = False
        self._parents: dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent

    # -- plumbing ----------------------------------------------------------

    def add(self, node: ast.AST, rule: str, message: str) -> None:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        snippet = self.lines[line - 1].strip() if line <= len(self.lines) else ""
        self.findings.append(Finding(self.rel, line, col, rule, message, snippet))

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Dotted chain with import aliases canonicalised (jnp -> jax.numpy)."""
        dotted = _dotted(node)
        if dotted is None:
            return None
        head, _, rest = dotted.partition(".")
        head = self.aliases.get(head, head)
        return f"{head}.{rest}" if rest else head

    def _is(self, node: ast.AST, *names: str) -> bool:
        return self.resolve(node) in names

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        while node in self._parents:
            node = self._parents[node]
            yield node

    # -- import / jit-binding collection -----------------------------------

    def collect(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.aliases[a.asname or a.name] = f"{node.module}.{a.name}"
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs.setdefault(node.name, node)  # type: ignore[arg-type]

        jit_names = ("jax.jit", "jax.numpy.jit")
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and self._is(
                    node.func, "jax.shard_map", "jax.experimental.shard_map.shard_map", "shard_map"):
                self.uses_shard_map = True
            if isinstance(node, ast.Call) and self._is(node.func, *jit_names):
                target = self._assign_target(node)
                statics = self._static_names(node)
                wrapped = node.args[0] if node.args else None
                if target:
                    self.jitted_names.add(target)
                    self.static_params[target] = statics
                if wrapped is not None and statics:
                    self._check_static_defaults(node, wrapped, statics)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    statics: set[str] = set()
                    jitted = False
                    if self._is(deco, *jit_names):
                        jitted = True
                    elif (isinstance(deco, ast.Call)
                          and self._is(deco.func, *jit_names)):
                        jitted, statics = True, self._static_names(deco)
                    elif (isinstance(deco, ast.Call)
                          and self._is(deco.func, "functools.partial", "partial")
                          and deco.args and self._is(deco.args[0], *jit_names)):
                        jitted, statics = True, self._static_names(deco)
                    if jitted:
                        self.jitted_names.add(node.name)
                        self.static_params[node.name] = statics
                        if statics:
                            self._check_def_static_defaults(node, statics)

    def _assign_target(self, call: ast.Call) -> Optional[str]:
        parent = self._parents.get(call)
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
            return _dotted(parent.targets[0])
        if isinstance(parent, ast.AnnAssign):
            return _dotted(parent.target)
        return None

    def _static_names(self, call: ast.Call) -> set[str]:
        val = _kw(call, "static_argnames")
        return set(_const_strings(val)) if val is not None else set()

    # -- JL001: recompile hazards ------------------------------------------

    _UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                   ast.SetComp, ast.GeneratorExp)

    def _check_static_defaults(self, call: ast.Call, wrapped: ast.AST,
                               statics: set[str]) -> None:
        name = _dotted(wrapped)
        fn = self.defs.get(name) if name else None
        if fn is not None:
            self._check_def_static_defaults(fn, statics, at=call)

    def _check_def_static_defaults(self, fn, statics: set[str],
                                   at: Optional[ast.AST] = None) -> None:
        args = fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
        defaults = ([None] * (len(fn.args.posonlyargs + fn.args.args)
                              - len(fn.args.defaults))
                    + list(fn.args.defaults) + list(fn.args.kw_defaults))
        for arg, default in zip(args, defaults):
            if arg.arg in statics and isinstance(default, self._UNHASHABLE):
                self.add(at or default, "JL001",
                         f"static arg {arg.arg!r} of {fn.name!r} has an "
                         "unhashable default — jit raises at dispatch; use a "
                         "tuple / frozen dataclass")

    def _jl001_callsites(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        statics = self.static_params.get(name or "")
        if not statics:
            return
        for kw in node.keywords:
            if kw.arg in statics and isinstance(kw.value, self._UNHASHABLE):
                self.add(kw.value, "JL001",
                         f"unhashable literal bound to static arg {kw.arg!r} "
                         f"of jitted {name!r}")

    def _jl001_host_arrays(self, node: ast.Call) -> None:
        if not self.uses_shard_map:
            return
        if self._is(node.func, "jax.device_put"):
            self.add(node, "JL001",
                     "jax.device_put in a shard_map module builds host-side "
                     "sharding state — a host-built array splits the C++ "
                     "dispatch cache on sharding-object identity even at "
                     "identical placement; produce it from a jitted init "
                     "sharing out_specs")
            return
        resolved = self.resolve(node.func) or ""
        if (resolved.startswith("jax.numpy.")
                and resolved.rsplit(".", 1)[1] in HOST_ARRAY_CTORS):
            parent = self._parents.get(node)
            # only attribute state (self.x = jnp.zeros(...)) — locals feeding
            # a jitted init are the recommended pattern, not a hazard
            while isinstance(parent, (ast.Call, ast.Attribute, ast.Tuple,
                                      ast.BinOp)):
                parent = self._parents.get(parent)
            if isinstance(parent, (ast.Assign, ast.AnnAssign)):
                targets = (parent.targets
                           if isinstance(parent, ast.Assign)
                           else [parent.target])
                for t in targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        self.add(node, "JL001",
                                 f"host-built array ({resolved.rsplit('.', 1)[1]}) "
                                 "assigned to instance state in a shard_map "
                                 "module — dispatch-cache split hazard (PR 9); "
                                 "build device state via a jitted init")
                        return

    # -- JL002: fixed-shape violations -------------------------------------

    def _jl002(self, node: ast.AST) -> None:
        if not self.in_jl002_scope:
            return
        if isinstance(node, ast.Call):
            resolved = self.resolve(node.func) or ""
            short = resolved.rsplit(".", 1)[-1]
            if resolved.startswith("jax.numpy."):
                if short in ("nonzero", "flatnonzero", "unique", "unique_values",
                             "argwhere") and _kw(node, "size") is None:
                    self.add(node, "JL002",
                             f"jnp.{short} without size= has data-dependent "
                             "output shape — untraceable under jit; pass "
                             "size= (+ fill_value)")
                elif short == "where" and len(node.args) == 1 and not node.keywords:
                    self.add(node, "JL002",
                             "one-arg jnp.where has data-dependent shape; use "
                             "the three-arg form or size=")
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "reshape"
                    and self._data_dependent_shape(node)):
                self.add(node, "JL002",
                         "reshape to a data-dependent extent — fixed-shape "
                         "jitted state requires static shapes")
        if isinstance(node, ast.Subscript):
            sl = node.slice
            if isinstance(sl, (ast.Compare, ast.BoolOp)) or (
                    isinstance(sl, ast.UnaryOp) and isinstance(sl.op, ast.Not)):
                self.add(node, "JL002",
                         "boolean-mask indexing produces a data-dependent "
                         "shape; use jnp.where(mask, x, fill) or size=-bounded "
                         "nonzero")

    def _data_dependent_shape(self, call: ast.Call) -> bool:
        for arg in call.args:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Call):
                    if (isinstance(sub.func, ast.Attribute)
                            and sub.func.attr == "item"):
                        return True
                    resolved = self.resolve(sub.func) or ""
                    if resolved.startswith("jax.numpy.") and resolved.rsplit(
                            ".", 1)[1] in ("sum", "count_nonzero", "max", "min"):
                        return True
        return False

    # -- JL003: host sync in device loops ----------------------------------

    _TIMERS = ("time.perf_counter", "time.time", "time.monotonic",
               "time.perf_counter_ns", "time.monotonic_ns")

    def _timed_region(self, node: ast.AST) -> bool:
        """Nearest enclosing function times itself -> measurement code."""
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(anc):
                    if isinstance(sub, ast.Call) and self._is(sub.func,
                                                              *self._TIMERS):
                        return True
                return False
        return False

    def _in_loop(self, node: ast.AST) -> bool:
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.For, ast.While)):
                return True
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
        return False

    def _mentions_jnp(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            resolved = self.resolve(sub) if isinstance(
                sub, (ast.Name, ast.Attribute)) else None
            if resolved and (resolved == "jax.numpy"
                             or resolved.startswith("jax.numpy.")):
                return True
        return False

    def _jl003(self, node: ast.Call) -> None:
        if not self._in_loop(node):
            return
        sync: Optional[str] = None
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item":
            sync = ".item()"
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr == "block_until_ready"):
            sync = ".block_until_ready()"
        elif self._is(node.func, "jax.block_until_ready"):
            sync = "jax.block_until_ready"
        elif self._is(node.func, "jax.device_get"):
            sync = "jax.device_get"
        elif self._is(node.func, "numpy.asarray", "numpy.array"):
            sync = "np." + node.func.attr  # type: ignore[union-attr]
        elif (isinstance(node.func, ast.Name)
              and node.func.id in ("float", "int", "bool")
              and node.args and self._mentions_jnp(node.args[0])):
            sync = f"{node.func.id}() on a jnp expression"
        if sync is None:
            return
        if self._timed_region(node):
            return
        self.add(node, "JL003",
                 f"{sync} inside a loop body forces a device sync per "
                 "iteration; hoist it out of the loop or keep the value on "
                 "device (timed regions are exempt)")

    # -- JL004: distance-contract completeness -----------------------------

    def _jl004_class(self, node: ast.ClassDef) -> None:
        defined: set[str] = set()
        for stmt in node.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defined.add(stmt.name)
            elif isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        defined.add(t.id)
            elif isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target,
                                                                ast.Name):
                defined.add(stmt.target.id)
        markers = defined & PAIR_DISTANCE_MARKERS
        if len(markers) >= 2:
            missing = sorted(PAIR_DISTANCE_METHODS - defined)
            if missing:
                self.add(node, "JL004",
                         f"class {node.name!r} implements part of the "
                         "PairDistance batched-method set but is missing "
                         f"{missing} — engines/scheduler/kernels call the "
                         "full contract")

    def _jl004_policy_kinds(self) -> None:
        kinds: list[str] = []
        kinds_node: Optional[ast.AST] = None
        policy_cls: Optional[ast.ClassDef] = None
        for node in ast.walk(self.tree):
            if (isinstance(node, ast.Assign)
                    and any(isinstance(t, ast.Name) and t.id == "POLICY_KINDS"
                            for t in node.targets)):
                kinds = _const_strings(node.value)
                kinds_node = node
            if isinstance(node, ast.ClassDef) and node.name == "DistancePolicy":
                policy_cls = node
        if not kinds or policy_cls is None:
            return
        handled = set(_const_strings(policy_cls))
        for kind in kinds:
            if kind not in handled:
                self.add(kinds_node, "JL004",
                         f"policy kind {kind!r} is registered in POLICY_KINDS "
                         "but never referenced inside DistancePolicy — "
                         "half-shipped contract (parse/bind will fall through)")

    # -- JL005: weak-type scalars at jit boundaries ------------------------

    def _jl005(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        if name not in self.jitted_names:
            return
        statics = self.static_params.get(name, set())
        for arg in node.args:
            if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, (int, float)) and not isinstance(arg.value, bool):
                self.add(arg, "JL005",
                         f"bare Python scalar {arg.value!r} passed to jitted "
                         f"{name!r} enters the trace weakly typed — wrap in "
                         "jnp.asarray(..., dtype) or make the param static")
        for kw in node.keywords:
            if kw.arg in statics:
                continue
            if isinstance(kw.value, ast.Constant) and isinstance(
                    kw.value.value, (int, float)) and not isinstance(
                    kw.value.value, bool):
                self.add(kw.value, "JL005",
                         f"bare Python scalar {kw.value.value!r} passed to "
                         f"jitted {name!r} (kwarg {kw.arg!r}) enters the trace "
                         "weakly typed — wrap in jnp.asarray(..., dtype) or "
                         "make the param static")

    # -- driver ------------------------------------------------------------

    def run(self) -> list[Finding]:
        self.collect()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                self._jl001_callsites(node)
                self._jl001_host_arrays(node)
                self._jl003(node)
                self._jl005(node)
            if isinstance(node, ast.ClassDef):
                self._jl004_class(node)
            self._jl002(node)
        self._jl004_policy_kinds()
        return self._apply_suppressions()

    def _apply_suppressions(self) -> list[Finding]:
        suppressed: dict[int, set[str]] = {}
        for i, line in enumerate(self.lines, start=1):
            m = SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            rules &= set(RULES)
            if not rules:
                continue
            target = i
            if line.strip().startswith("#"):  # standalone comment: next line
                target = i + 1
            suppressed.setdefault(target, set()).update(rules)
        return [f for f in self.findings
                if f.rule not in suppressed.get(f.line, set())]


# ---------------------------------------------------------------------------
# tree scan + baseline

def lint_file(path: pathlib.Path, root: pathlib.Path) -> list[Finding]:
    rel = path.resolve().relative_to(root.resolve()).as_posix()
    in_scope = any(rel.startswith(p + "/") or rel == p for p in JL002_SCOPE)
    try:
        source = path.read_text()
        lint = _FileLint(path, rel, source, in_scope)
    except (SyntaxError, UnicodeDecodeError) as e:
        return [Finding(rel, getattr(e, "lineno", 1) or 1, 0, "JL000",
                        f"unparseable: {e.msg if hasattr(e, 'msg') else e}", "")]
    return sorted(lint.run(), key=lambda f: (f.line, f.col, f.rule))


def lint_tree(root: pathlib.Path,
              targets: Iterable[str] = DEFAULT_TARGETS) -> list[Finding]:
    findings: list[Finding] = []
    for target in targets:
        base = (root / target) if not pathlib.Path(target).is_absolute() \
            else pathlib.Path(target)
        if base.is_file():
            findings.extend(lint_file(base, root))
            continue
        for path in sorted(base.rglob("*.py")):
            findings.extend(lint_file(path, root))
    return findings


def fingerprints(findings: Iterable[Finding]) -> dict[str, Finding]:
    """Fingerprint -> finding; duplicate (path, rule, snippet) keys get
    ordinals so N identical lines need N baseline entries."""
    seen: dict[tuple, int] = {}
    out: dict[str, Finding] = {}
    for f in findings:
        key = (f.path, f.rule, f.snippet)
        occ = seen.get(key, 0)
        seen[key] = occ + 1
        out[f.fingerprint(occ)] = f
    return out


def load_baseline(path: pathlib.Path) -> set[str]:
    if not path.is_file():
        return set()
    data = json.loads(path.read_text())
    return {entry["fingerprint"] for entry in data.get("findings", [])}


def write_baseline(path: pathlib.Path, findings: list[Finding]) -> None:
    fps = fingerprints(findings)
    payload = {
        "comment": "jaxlint accepted-debt baseline; regenerate with "
                   "`python tools/jaxlint --update-baseline`. Entries are "
                   "line-insensitive (file + rule + source text).",
        "findings": [
            {"fingerprint": fp, "rule": f.rule, "path": f.path,
             "snippet": f.snippet}
            for fp, f in sorted(fps.items(), key=lambda kv: (kv[1].path,
                                                             kv[1].line))
        ],
    }
    path.write_text(json.dumps(payload, indent=2) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="jaxlint", description=__doc__)
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to lint (default: {DEFAULT_TARGETS})")
    ap.add_argument("--root", default=".", help="repository root")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: tools/jaxlint/baseline.json "
                         "under --root)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept current findings as the new baseline")
    ap.add_argument("--report", default=None,
                    help="write a JSON report (all findings + status) here")
    args = ap.parse_args(argv)

    root = pathlib.Path(args.root).resolve()
    baseline_path = (pathlib.Path(args.baseline) if args.baseline
                     else root / "tools" / "jaxlint" / "baseline.json")
    targets = tuple(args.paths) or DEFAULT_TARGETS

    findings = lint_tree(root, targets)
    fps = fingerprints(findings)

    if args.update_baseline:
        write_baseline(baseline_path, findings)
        print(f"jaxlint: baseline updated with {len(findings)} finding(s) "
              f"-> {baseline_path}")
        return 0

    baseline = set() if args.no_baseline else load_baseline(baseline_path)
    new = {fp: f for fp, f in fps.items() if fp not in baseline}
    stale = baseline - set(fps)

    if args.report:
        pathlib.Path(args.report).write_text(json.dumps({
            "total": len(findings),
            "baselined": len(fps) - len(new),
            "new": [dataclasses.asdict(f) for f in new.values()],
            "stale_baseline_entries": sorted(stale),
        }, indent=2) + "\n")

    for f in sorted(new.values(), key=lambda f: (f.path, f.line, f.col)):
        print(f.render(), file=sys.stderr)
    if stale:
        print(f"jaxlint: note: {len(stale)} stale baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'} (fixed debt — run "
              "--update-baseline to shrink the baseline)", file=sys.stderr)
    if new:
        print(f"jaxlint: {len(new)} new finding(s) "
              f"({len(fps) - len(new)} baselined)", file=sys.stderr)
        return 1
    print(f"jaxlint: clean ({len(fps)} baselined finding(s), "
          f"{len(RULES)} rules)")
    return 0
