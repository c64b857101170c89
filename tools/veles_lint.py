"""veles_lint — project-specific static analysis for the serving tier
(ISSUE 15).

Three review-hardening rounds (PRs 11, 12, 14) each found real
concurrency violations by hand; this tool makes the rules they were
checking executable.  Two static passes (the runtime third — the
lock-order witness — lives in ``veles_tpu/serving/lockcheck.py``):

LOCK DISCIPLINE.  A class declares which attributes its lock guards::

    class Router:
        _guarded_by = {"_live": "_lock", "_jobs": "_lock"}

or per attribute, with a trailing comment on the assignment::

    self._queue = collections.deque()   # guarded-by: _cond

The pass walks every method and flags any read or write of a guarded
attribute that is not (a) inside a ``with self.<lock>:`` block, (b) in
a method marked ``# caller-holds: <lock>`` (placed on the ``def`` line
or directly under it, before the first real statement), or (c) in
``__init__`` (no concurrency before construction completes).  A call
``self.helper()`` where ``helper`` is marked ``# caller-holds: X``
and ``X`` is not held at the call site is flagged too — the broken
caller-holds CHAIN is exactly the bug class PR 12's review caught by
hand.  Module-level globals ride the same pass via a trailing
``# guarded-by: <lock>`` on the global's assignment (the metrics
registry, the default telemetry store).

Classes that are deliberately lock-free declare why::

    _synchronized_externally = "engine worker thread (single owner)"

TRACED PURITY.  Every function the engine jits or scans — discovered
from ``self._jit(...)`` / ``jax.jit(...)`` / ``lax.scan(...)`` call
sites plus the explicit ``TRACED_REGISTRY`` below — must be pure host-
side: the pass walks its call graph (same module, and one import hop
into project modules) and flags ``time.*``, ``random`` /
``numpy.random`` (``veles_tpu.prng`` is exempt — counter-based,
trace-safe by design), threading primitives, ``print``, and mutation
of closed-over containers.  A ``time.time()`` baked into a scanned
body is a constant at trace time — the class of bug that silently
costs a TPU window (PAPERS.md, the Julia-to-TPU compilation paper).

SUPPRESSIONS are per-site, named and greppable::

    x = self._queue  # lint: allow(lock-discipline): benign racy peek

Every suppression must carry a non-empty reason; a reasonless or
UNUSED suppression is itself a finding, so the exception list can
never rot.

Run standalone (``python tools/veles_lint.py --check``) — findings to
stderr, one bench.py-style summary record streamed to stdout — or via
tier-1 (``tests/test_lint.py`` runs the full-tree check), so a future
unguarded access fails the suite, not a review round.
"""

from __future__ import annotations

import argparse
import ast
import io
import json
import os
import re
import sys
import tokenize

TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TOOLS_DIR)

#: the serving modules the lock-discipline pass covers (ISSUE 15) —
#: every module that owns a lock or declares external synchronization
SERVING_MODULES = (
    "veles_tpu/serving/lm_engine.py",
    "veles_tpu/serving/router.py",
    "veles_tpu/serving/batcher.py",
    "veles_tpu/serving/kv_pool.py",
    "veles_tpu/serving/metrics.py",
    "veles_tpu/serving/tracing.py",
    "veles_tpu/serving/timeseries.py",
    "veles_tpu/serving/slo.py",
    "veles_tpu/serving/model_manager.py",
    "veles_tpu/serving/faults.py",
    "veles_tpu/serving/lockcheck.py",
)

#: traced-purity entry points beyond what call-site discovery finds:
#: (path suffix, bare function name) — functions RETURNED by builders
#: and jitted indirectly, or library functions every traced body runs
TRACED_REGISTRY = (
    ("veles_tpu/serving/lm_engine.py", "mega_plain"),
    ("veles_tpu/serving/lm_engine.py", "mega_spec"),
    ("veles_tpu/serving/lm_engine.py", "plain_iter"),
    ("veles_tpu/serving/lm_engine.py", "spec_iter"),
    ("veles_tpu/ops/transformer.py", "propose_draft_in_graph"),
)

#: modules the purity pass scans for jit/scan call sites
PURITY_MODULES = (
    "veles_tpu/serving/lm_engine.py",
    "veles_tpu/ops/transformer.py",
)

#: hot-path methods the host-sync pass covers (ISSUE 17): each entry
#: must EXIST and carry a trailing ``# hot-path`` marker on its def
#: line — the drift check that keeps a rename from silently shrinking
#: the analysis set (the TRACED_REGISTRY discipline, applied here)
HOT_PATH_REGISTRY = (
    ("veles_tpu/serving/lm_engine.py", "_admit"),
    ("veles_tpu/serving/lm_engine.py", "_admit_paged"),
    ("veles_tpu/serving/lm_engine.py", "_cow_guard"),
    ("veles_tpu/serving/lm_engine.py", "_prepare_chunk_paged"),
    ("veles_tpu/serving/lm_engine.py", "_dispatch_chunk_paged"),
    ("veles_tpu/serving/lm_engine.py", "_dispatch_decode"),
    ("veles_tpu/serving/lm_engine.py", "_fetch_flights"),
    ("veles_tpu/serving/lm_engine.py", "_prepare_step"),
    ("veles_tpu/serving/lm_engine.py", "_under_step"),
    ("veles_tpu/serving/lm_engine.py", "_deliver"),
    ("veles_tpu/serving/lm_engine.py", "_step_plain"),
    ("veles_tpu/serving/lm_engine.py", "_step_speculative"),
    ("veles_tpu/serving/lm_engine.py", "_step_megastep"),
    ("veles_tpu/serving/lm_engine.py", "_serve_loop"),
    ("veles_tpu/serving/batcher.py", "_take_batch"),
    ("veles_tpu/serving/batcher.py", "_dispatch"),
    ("veles_tpu/serving/batcher.py", "_serve_batches"),
    ("veles_tpu/serving/router.py", "_place"),
)

#: modules whose ``self._X_jit = self._jit(...)`` sites must each
#: carry a ``# programs: <family>`` census comment (ISSUE 17): the
#: declared program-family census the jit-guard fixtures are checked
#: against, so a silently-compiled twin (the PR 8 GSPMD bug class) is
#: a lint finding, not a _cache_size() audit
CENSUS_MODULES = ("veles_tpu/serving/lm_engine.py",)

#: jit-guard fixture files: every family the census declares must be
#: compile-count-asserted here, and vice versa
JIT_GUARD_FIXTURES = ("tests/lm_cases.py",)

CHECKS = ("lock-discipline", "traced-purity", "suppression",
          "recompile-hazard", "host-sync", "resource-lifecycle")

#: per-pass exit-code bits — ``main`` returns their OR, so CI can tell
#: WHICH pass failed from the exit status alone (pinned by
#: tests/test_lint.py so a pass dropping out of the default set fails
#: loudly)
PASS_BITS = {
    "lock-discipline": 1,
    "traced-purity": 2,
    "suppression": 4,
    "recompile-hazard": 8,
    "host-sync": 16,
    "resource-lifecycle": 32,
}

SUPPRESS_RE = re.compile(
    r"#\s*lint:\s*allow\((?P<check>[\w-]+)\)\s*:?\s*(?P<reason>.*)")
GUARDED_RE = re.compile(r"#\s*guarded-by:\s*(?P<lock>\w+)")
HOLDS_RE = re.compile(r"#\s*caller-holds:\s*(?P<locks>[\w\s,]+)")
PROGRAMS_RE = re.compile(r"#\s*programs:\s*(?P<family>\w+)")
HOT_PATH_RE = re.compile(r"#\s*hot-path\b")
#: family references a jit-guard fixture makes: ``engine._step_jit``
FIXTURE_FAMILY_RE = re.compile(r"\._(\w+)_jit\b")

#: mutating container methods (closed-over mutation detection)
MUTATORS = frozenset((
    "append", "extend", "insert", "remove", "pop", "popleft",
    "appendleft", "clear", "update", "setdefault", "add", "discard",
    "sort", "reverse",
))

#: dotted-call prefixes that are impure on a traced path
IMPURE_PREFIXES = (
    "time.", "random.", "numpy.random.", "np.random.", "threading.",
    "os.urandom", "secrets.",
)
IMPURE_BARE = frozenset(("print", "input", "open"))

#: prefixes exempt from the random rule — the project's counter-based
#: PRNG is trace-safe by design (veles_tpu/prng.py)
PURE_PREFIXES = ("prng.",)


class Finding:
    __slots__ = ("file", "line", "check", "message")

    def __init__(self, file, line, check, message):
        self.file = file
        self.line = int(line)
        self.check = check
        self.message = message

    def __repr__(self):
        return "%s:%d: [%s] %s" % (self.file, self.line, self.check,
                                   self.message)

    def to_dict(self):
        return {"file": self.file, "line": self.line,
                "check": self.check, "message": self.message}


class Suppression:
    __slots__ = ("file", "line", "check", "reason", "standalone",
                 "used")

    def __init__(self, file, line, check, reason, standalone):
        self.file = file
        self.line = int(line)
        self.check = check
        self.reason = reason.strip()
        #: a comment-only line (covers the statement BELOW it); a
        #: trailing comment covers its own line only
        self.standalone = bool(standalone)
        self.used = False


def _comments(src):
    """({lineno: comment text}, {standalone linenos}) over ``src`` —
    standalone marks comment-only lines (tokenize survives anything
    that parses as Python)."""
    out, standalone = {}, set()
    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
                if not tok.line[:tok.start[1]].strip():
                    standalone.add(tok.start[0])
    except tokenize.TokenError:
        pass
    return out, standalone


def _suppressions(relpath, comments, standalone):
    """Every ``# lint: allow(check): reason`` site in the file, plus a
    finding for each malformed one (unknown check / missing reason)."""
    sups, findings = [], []
    for line, text in comments.items():
        m = SUPPRESS_RE.search(text)
        if not m:
            continue
        check, reason = m.group("check"), m.group("reason").strip()
        if check not in CHECKS:
            findings.append(Finding(
                relpath, line, "suppression",
                "unknown check %r in suppression (one of %r)"
                % (check, CHECKS)))
            continue
        if not reason:
            findings.append(Finding(
                relpath, line, "suppression",
                "suppression carries no reason string — every "
                "exception must say why"))
            continue
        sups.append(Suppression(relpath, line, check, reason,
                                line in standalone))
    return sups, findings


def _suppressed(sups, line, check):
    """A TRAILING suppression covers exactly its own line; a
    STANDALONE comment-line suppression covers exactly the statement
    directly below it — never both, so one comment can never swallow
    a second, unrelated finding on the next line."""
    for s in sups:
        if s.check == check \
                and line == (s.line + 1 if s.standalone else s.line):
            s.used = True
            return True
    return False


def _dotted(node):
    """'a.b.c' for an Attribute/Name chain, or None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# --------------------------------------------------------------- lock pass
def _caller_holds(fn, comments):
    """The locks a method declares its caller holds: a ``#
    caller-holds: X[, Y]`` comment on the ``def`` line or between it
    and the first real (non-docstring) statement."""
    if not fn.body:
        return frozenset()
    first = fn.body[0]
    end = first.lineno
    if (isinstance(first, ast.Expr)
            and isinstance(first.value, ast.Constant)
            and isinstance(first.value.value, str)):
        end = (fn.body[1].lineno if len(fn.body) > 1
               else first.end_lineno or first.lineno)
    locks = set()
    for line in range(fn.lineno, end + 1):
        m = HOLDS_RE.search(comments.get(line, ""))
        if m:
            locks.update(x.strip() for x in
                         m.group("locks").split(",") if x.strip())
    return frozenset(locks)


class _ClassLint:
    """Lock-discipline over one class: guard map, caller-holds chain,
    with-block tracking."""

    def __init__(self, relpath, cls, comments, sups, findings):
        self.relpath = relpath
        self.cls = cls
        self.comments = comments
        self.sups = sups
        self.findings = findings
        self.guard = {}          # attr -> lock
        self.external = None
        self.holds = {}          # method name -> frozenset(locks)
        self._collect()
        self.locks = frozenset(self.guard.values())

    def _collect(self):
        for node in self.cls.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                if name == "_guarded_by" \
                        and isinstance(node.value, ast.Dict):
                    for k, v in zip(node.value.keys, node.value.values):
                        if isinstance(k, ast.Constant) \
                                and isinstance(v, ast.Constant):
                            self.guard[str(k.value)] = str(v.value)
                elif name == "_synchronized_externally" \
                        and isinstance(node.value, ast.Constant):
                    self.external = str(node.value.value)
                    if not self.external.strip():
                        self.findings.append(Finding(
                            self.relpath, node.lineno, "lock-discipline",
                            "_synchronized_externally must name the "
                            "owner (empty string)"))
        # trailing `# guarded-by:` comments on self.<attr> assignments
        for fn in self._methods():
            for node in ast.walk(fn):
                if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                    continue
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                m = GUARDED_RE.search(
                    self.comments.get(node.lineno, ""))
                if not m:
                    continue
                for t in targets:
                    if isinstance(t, ast.Attribute) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id == "self":
                        self.guard[t.attr] = m.group("lock")
        for fn in self._methods():
            self.holds[fn.name] = _caller_holds(fn, self.comments)

    def _methods(self):
        return [n for n in self.cls.body
                if isinstance(n, (ast.FunctionDef,
                                  ast.AsyncFunctionDef))]

    def run(self):
        if not self.guard:
            return
        for fn in self._methods():
            if fn.name == "__init__":
                continue
            self._walk_stmts(fn.body, self.holds.get(fn.name,
                                                     frozenset()))

    def _lock_of_with_item(self, item):
        expr = item.context_expr
        if isinstance(expr, ast.Attribute) \
                and isinstance(expr.value, ast.Name) \
                and expr.value.id == "self" and expr.attr in self.locks:
            return expr.attr
        return None

    def _walk_stmts(self, stmts, held):
        for stmt in stmts:
            self._walk(stmt, held)

    def _walk(self, node, held):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            newly = set()
            for item in node.items:
                lock = self._lock_of_with_item(item)
                if lock:
                    newly.add(lock)
                else:
                    self._walk(item.context_expr, held)
            self._walk_stmts(node.body, held | newly)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a nested function runs LATER, on whatever thread calls
            # it — it holds nothing unless it says so itself
            inner = _caller_holds(node, self.comments)
            self._walk_stmts(node.body, frozenset(inner))
            return
        if isinstance(node, ast.Lambda):
            self._walk(node.body, frozenset())
            return
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            attr = node.attr
            lock = self.guard.get(attr)
            if lock is not None and lock not in held \
                    and not _suppressed(self.sups, node.lineno,
                                        "lock-discipline"):
                kind = ("write" if isinstance(node.ctx, (ast.Store,
                                                         ast.Del))
                        else "read")
                self.findings.append(Finding(
                    self.relpath, node.lineno, "lock-discipline",
                    "%s of %s.%s (guarded by %s) outside `with "
                    "self.%s:` and no `# caller-holds: %s` marker"
                    % (kind, self.cls.name, attr, lock, lock, lock)))
            return      # leaf: Name('self') below needs no recursion
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "self":
            callee = node.func.attr
            missing = self.holds.get(callee, frozenset()) - held
            if missing and not _suppressed(self.sups, node.lineno,
                                           "lock-discipline"):
                self.findings.append(Finding(
                    self.relpath, node.lineno, "lock-discipline",
                    "call to %s.%s() (# caller-holds: %s) without "
                    "holding %s — caller-holds chain broken"
                    % (self.cls.name, callee,
                       ", ".join(sorted(self.holds[callee])),
                       ", ".join(sorted(missing)))))
            for arg in list(node.args) + [kw.value
                                          for kw in node.keywords]:
                self._walk(arg, held)
            return
        for child in ast.iter_child_nodes(node):
            self._walk(child, held)


class _ModuleGlobalsLint:
    """Lock discipline over module-level globals: ``# guarded-by:``
    trailing a top-level assignment makes every module-level
    function's access of that global require ``with <lock>:``."""

    def __init__(self, relpath, tree, comments, sups, findings):
        self.relpath = relpath
        self.tree = tree
        self.comments = comments
        self.sups = sups
        self.findings = findings
        self.guard = {}
        for node in tree.body:
            if isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                m = GUARDED_RE.search(comments.get(node.lineno, ""))
                if m:
                    self.guard[node.targets[0].id] = m.group("lock")
        self.locks = frozenset(self.guard.values())

    def run(self):
        if not self.guard:
            return
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                self._walk_stmts(node.body, frozenset())

    def _walk_stmts(self, stmts, held):
        for stmt in stmts:
            self._walk(stmt, held)

    def _walk(self, node, held):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            newly = set()
            for item in node.items:
                expr = item.context_expr
                if isinstance(expr, ast.Name) and expr.id in self.locks:
                    newly.add(expr.id)
            self._walk_stmts(node.body, held | newly)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            body = node.body if isinstance(node.body, list) \
                else [node.body]
            self._walk_stmts(body, frozenset())
            return
        if isinstance(node, ast.Name) and node.id in self.guard:
            lock = self.guard[node.id]
            if lock not in held \
                    and not _suppressed(self.sups, node.lineno,
                                        "lock-discipline"):
                self.findings.append(Finding(
                    self.relpath, node.lineno, "lock-discipline",
                    "access of module global %s (guarded by %s) "
                    "outside `with %s:`" % (node.id, lock, lock)))
            return
        for child in ast.iter_child_nodes(node):
            self._walk(child, held)


# ---------------------------------------------------------- shared parse
class _ModuleIndex:
    """ONE parse of one module, shared by every pass (ISSUE 17
    satellite: ``--check`` used to re-read and re-``ast.parse`` the
    tree once per pass): source, tree, comments, suppressions, defs by
    bare name, one-hop project imports."""

    def __init__(self, root, relpath):
        self.relpath = relpath
        path = os.path.join(root, relpath)
        with open(path, "r", encoding="utf-8") as f:
            self.src = f.read()
        self.tree = ast.parse(self.src, filename=relpath)
        self.comments, self.standalone = _comments(self.src)
        self.defs = {}           # bare name -> [FunctionDef]
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                self.defs.setdefault(node.name, []).append(node)
        #: imported name -> project-relative module path (one hop)
        self.imports = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.module.startswith("veles_tpu"):
                mod_rel = node.module.replace(".", "/") + ".py"
                for alias in node.names:
                    self.imports[alias.asname or alias.name] = \
                        (mod_rel, alias.name)
        self.sups, self.sup_findings = _suppressions(
            relpath, self.comments, self.standalone)


class _ModuleSet:
    """The per-run parse cache: every pass resolves modules through
    here, so each file is read and ``ast.parse``d exactly once per
    ``run_check`` regardless of how many passes touch it."""

    def __init__(self, root):
        self.root = root
        self._cache = {}

    def get(self, relpath):
        if relpath not in self._cache:
            try:
                self._cache[relpath] = _ModuleIndex(self.root, relpath)
            except (OSError, SyntaxError):
                self._cache[relpath] = None
        return self._cache[relpath]

    def parses(self):
        return sum(1 for m in self._cache.values() if m is not None)


# ------------------------------------------------------------- purity pass
class _PurityPass:
    """Traced-purity over discovered jit/scan targets + the registry;
    call graph followed same-module and one hop into project
    modules.  Records every (module, fn) it analyzes so the
    recompile-hazard pass walks the SAME traced set without its own
    discovery."""

    def __init__(self, modules, sups_by_file, findings):
        self.modules = modules
        self.sups_by_file = sups_by_file
        self.findings = findings
        self._analyzed = set()
        self.traced_functions = 0
        #: [(mod, fn)] in analysis order — the recompile pass's input
        self.analyzed = []

    def module(self, relpath):
        return self.modules.get(relpath)

    # ----------------------------------------------------------- discovery
    def discover(self, relpath):
        """Traced roots in ``relpath``: first args of self._jit /
        jax.jit / jit / (jax.)lax.scan calls, resolved through local
        ``name = vmap/partial/checkpoint(...)`` aliases."""
        mod = self.module(relpath)
        if mod is None:
            return []
        roots = []
        aliases = self._aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            name = _dotted(node.func)
            if name is None:
                continue
            traced = (name in ("jax.jit", "jit")
                      or name.endswith("._jit")
                      or name in ("lax.scan", "jax.lax.scan"))
            if not traced:
                continue
            roots.extend(self._resolve(node.args[0], mod, aliases))
        return roots

    def _aliases(self, tree):
        """name -> value expr for simple ``name = <call>`` bindings
        anywhere in the module (function-local included) — how
        ``propose_all = jax.vmap(<lambda>)`` resolves to the lambda."""
        out = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                out[node.targets[0].id] = node.value
        return out

    def _resolve(self, expr, mod, aliases, depth=0):
        """FunctionDef/Lambda nodes an expression can denote."""
        if depth > 6:
            return []
        if isinstance(expr, ast.Lambda):
            return [(mod, expr)]
        if isinstance(expr, ast.Name):
            name = expr.id
            if name in mod.defs:
                return [(mod, fn) for fn in mod.defs[name]]
            alias = aliases.get(name)
            if alias is not None:
                return self._resolve(alias, mod, aliases, depth + 1)
            return []
        if isinstance(expr, ast.Call):
            wrapper = _dotted(expr.func) or ""
            if wrapper.split(".")[-1] in ("vmap", "partial",
                                          "checkpoint", "remat",
                                          "named_call"):
                out = []
                for arg in expr.args:
                    out.extend(self._resolve(arg, mod, aliases,
                                             depth + 1))
                return out
        return []

    # ------------------------------------------------------------ analysis
    def analyze(self, mod, fn, depth=0):
        key = (mod.relpath, getattr(fn, "name", "<lambda>"),
               fn.lineno)
        if key in self._analyzed or depth > 8:
            return
        self._analyzed.add(key)
        self.traced_functions += 1
        self.analyzed.append((mod, fn))
        local = self._local_names(fn)
        aliases = self._aliases(fn) if not isinstance(fn, ast.Lambda) \
            else {}
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                self._check_node(mod, fn, node, local, aliases, depth)

    @staticmethod
    def _local_names(fn):
        names = set()
        args = fn.args
        for a in (args.args + args.posonlyargs + args.kwonlyargs
                  + ([args.vararg] if args.vararg else [])
                  + ([args.kwarg] if args.kwarg else [])):
            names.add(a.arg)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Name) \
                        and isinstance(node.ctx, ast.Store):
                    names.add(node.id)
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    names.add(node.name)
        return names

    def _flag(self, mod, node, message):
        sups = self.sups_by_file.get(mod.relpath, [])
        if _suppressed(sups, node.lineno, "traced-purity"):
            return
        self.findings.append(Finding(
            mod.relpath, node.lineno, "traced-purity", message))

    def _check_node(self, mod, fn, node, local, aliases, depth):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name:
                if any(name.startswith(p) for p in PURE_PREFIXES):
                    return
                if name in IMPURE_BARE:
                    self._flag(mod, node,
                               "%s() in a traced/scanned body — a "
                               "host side effect baked in at trace "
                               "time" % name)
                    return
                for p in IMPURE_PREFIXES:
                    if name.startswith(p) or name == p.rstrip("."):
                        self._flag(mod, node,
                                   "%s in a traced/scanned body — "
                                   "host-side nondeterminism is a "
                                   "trace-time constant" % name)
                        return
                # closed-over container mutation: obj.append(...) on a
                # name not local to the traced function
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr in MUTATORS \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.value.id not in local:
                    self._flag(mod, node,
                               "%s.%s() mutates a closed-over/global "
                               "container inside a traced body"
                               % (node.func.value.id, node.func.attr))
                    return
                # call-graph follow
                self._follow(mod, name, aliases, depth)
        elif isinstance(node, ast.AugAssign) \
                and isinstance(node.target, ast.Name) \
                and node.target.id not in local:
            self._flag(mod, node,
                       "augmented assignment to closed-over/global "
                       "%r inside a traced body" % node.target.id)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Subscript) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id not in local:
                    self._flag(mod, node,
                               "subscript store into closed-over/"
                               "global %r inside a traced body"
                               % t.value.id)

    def _follow(self, mod, name, aliases, depth):
        if "." in name:
            return          # dotted calls: library (jnp/jax/numpy) —
        targets = []        # flagged above if impure, else trusted
        if name in mod.defs:
            targets = [(mod, f) for f in mod.defs[name]]
        elif name in aliases:
            targets = self._resolve(aliases[name], mod,
                                    self._aliases(mod.tree))
        elif name in mod.imports:
            rel, orig = mod.imports[name]
            other = self.module(rel)
            if other is not None and orig in other.defs:
                targets = [(other, f) for f in other.defs[orig]]
        for m, f in targets:
            self.analyze(m, f, depth + 1)

    # -------------------------------------------------------------- driver
    def run(self, purity_modules=PURITY_MODULES,
            registry=TRACED_REGISTRY):
        for relpath in purity_modules:
            for mod, fn in self.discover(relpath):
                self.analyze(mod, fn)
        for relpath, name in registry:
            mod = self.module(relpath)
            if mod is None or name not in mod.defs:
                self.findings.append(Finding(
                    relpath, 1, "traced-purity",
                    "TRACED_REGISTRY names %r but no such function "
                    "exists — registry drift" % name))
                continue
            for fn in mod.defs[name]:
                self.analyze(mod, fn)


# ------------------------------------------------- recompile-hazard pass
class _RecompilePass:
    """Recompile hazards over the traced set the purity pass walked
    (ISSUE 17): (a) closure over ``self`` — a traced body reading a
    mutable attribute bakes its trace-time value in (or retraces per
    identity) instead of threading it as an argument; (b)
    shape-dependent Python branching — an ``if``/``while`` on
    ``.shape`` / ``len()`` specializes the program per shape, silently
    multiplying the compiled-program family; (c) Python concretization
    — ``int()``/``float()``/``bool()`` of a traced value either dies
    at trace time or bakes a per-call scalar into the program.  Plus
    the CENSUS: every ``self._X_jit = self._jit(...)`` site declares
    its program family (``# programs: <family>``), and the declared
    set must agree bidirectionally with what the jit-guard fixtures
    compile-count-assert — a compiled family nobody bounds is exactly
    the PR 8 silently-compiled-twin bug class."""

    def __init__(self, modules, sups_by_file, findings):
        self.modules = modules
        self.sups_by_file = sups_by_file
        self.findings = findings
        self.census_sites = 0

    def _flag(self, relpath, node, message):
        sups = self.sups_by_file.get(relpath, [])
        if _suppressed(sups, node.lineno, "recompile-hazard"):
            return
        self.findings.append(Finding(
            relpath, node.lineno, "recompile-hazard", message))

    # ------------------------------------------------------ traced bodies
    def run_bodies(self, analyzed):
        for mod, fn in analyzed:
            args = fn.args
            params = {a.arg for a in (
                args.args + args.posonlyargs + args.kwonlyargs
                + ([args.vararg] if args.vararg else [])
                + ([args.kwarg] if args.kwarg else []))}
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                for node in ast.walk(stmt):
                    self._check_node(mod, node, params)

    @staticmethod
    def _names_outside_static(expr):
        """Load Names in ``expr`` NOT under a static accessor
        (``.shape``/``.ndim``/``.dtype``) — ``float(1.0 / dh)`` where
        ``dh = q.shape[-1]`` concretizes nothing traced."""
        out = set()

        def rec(n):
            if isinstance(n, ast.Attribute) \
                    and n.attr in ("shape", "ndim", "dtype"):
                return
            if isinstance(n, ast.Name):
                out.add(n.id)
            for c in ast.iter_child_nodes(n):
                rec(c)

        rec(expr)
        return out

    def _check_node(self, mod, node, params):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            self._flag(mod.relpath, node,
                       "traced body closes over self.%s — mutable "
                       "engine state baked in at trace time; thread "
                       "it as an argument" % node.attr)
        elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
            for t in ast.walk(node.test):
                if isinstance(t, ast.Attribute) and t.attr == "shape":
                    self._flag(mod.relpath, node,
                               "Python branch on .shape inside a "
                               "traced body — one compiled program "
                               "per shape, a silent family multiplier")
                    return
                if isinstance(t, ast.Call) \
                        and isinstance(t.func, ast.Name) \
                        and t.func.id == "len":
                    self._flag(mod.relpath, node,
                               "Python branch on len() inside a "
                               "traced body — shape-dependent "
                               "control flow specializes per shape")
                    return
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Name) \
                and node.func.id in ("int", "float", "bool") \
                and node.args \
                and (self._names_outside_static(node.args[0])
                     & params):
            self._flag(mod.relpath, node,
                       "%s() of a traced argument inside a traced "
                       "body — concretizes a traced value (trace-"
                       "time error or a baked-in per-call constant)"
                       % node.func.id)

    # ------------------------------------------------------------- census
    def run_census(self, census_modules, jit_guard_fixtures):
        declared = {}        # family -> [(relpath, line)]
        for relpath in census_modules:
            mod = self.modules.get(relpath)
            if mod is None:
                continue
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1):
                    continue
                t = node.targets[0]
                if not (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                        and t.attr.endswith("_jit")):
                    continue
                call = node.value
                if not (isinstance(call, ast.Call)
                        and (_dotted(call.func) or "")
                        .endswith("._jit")):
                    continue       # e.g. `self._step_jit = None`
                self.census_sites += 1
                derived = t.attr[:-len("_jit")].lstrip("_")
                family = None
                for line in (node.lineno - 1, node.lineno):
                    m = PROGRAMS_RE.search(
                        mod.comments.get(line, ""))
                    if m:
                        family = m.group("family")
                if family is None:
                    self._flag(mod.relpath, node,
                               "jit site self.%s has no `# programs: "
                               "<family>` census entry — every "
                               "compiled family must be declared"
                               % t.attr)
                    continue
                if family != derived:
                    self._flag(mod.relpath, node,
                               "census declares family %r but the "
                               "site installs self.%s (family %r) — "
                               "the census lies" % (family, t.attr,
                                                    derived))
                    continue
                declared.setdefault(family, []).append(
                    (mod.relpath, node.lineno))
        asserted = {}        # family -> (fixture relpath, line)
        for relpath in jit_guard_fixtures:
            mod = self.modules.get(relpath)
            if mod is None:
                continue
            for i, line in enumerate(mod.src.splitlines(), start=1):
                for m in FIXTURE_FAMILY_RE.finditer(line):
                    asserted.setdefault(m.group(1), (relpath, i))
        if not census_modules or not jit_guard_fixtures:
            return
        for family in sorted(set(declared) - set(asserted)):
            rel, line = declared[family][0]
            self.findings.append(Finding(
                rel, line, "recompile-hazard",
                "program family %r is compiled but no jit-guard "
                "fixture bounds its compile count — a silently-"
                "compiled twin would go unnoticed (add it to %s)"
                % (family, ", ".join(jit_guard_fixtures))))
        for family in sorted(set(asserted) - set(declared)):
            rel, line = asserted[family]
            self.findings.append(Finding(
                rel, line, "recompile-hazard",
                "jit-guard fixture asserts family %r but no census "
                "site declares it — fixture drift" % family))


# --------------------------------------------------------- host-sync pass
#: dispatch sites: a call through one of these produces DEVICE values
#: and counts as an un-fenced in-flight program until read back
_DISPATCH_SUFFIX = "_jit"
_DISPATCH_NAMES = frozenset(("self.forward",))
#: explicit device→host reads: their results are HOST values, and
#: reaching one fences the in-flight dispatch
_CLEANSERS = frozenset(("xfer.to_host", "jax.device_get",
                        "device_get"))
_TIMING_CALLS = frozenset(("time.monotonic", "time.perf_counter",
                           "time.time"))
_SYNC_BUILTINS = frozenset(("int", "float", "bool"))
_SYNC_ASARRAY = frozenset(("numpy.asarray", "np.asarray",
                           "numpy.array", "np.array"))
_SYNC_METHODS = frozenset(("item", "tolist", "__array__"))


class _HostSyncPass:
    """Implicit device→host syncs in ``# hot-path`` methods (ISSUE
    17): taint names bound from jit dispatches, then flag host
    coercions of tainted values (``int()``/``float()``/``bool()``/
    ``numpy.asarray``/``.item()``/``.tolist()``), ``jnp.*`` staging
    (implicit host→device), timing subtractions taken while a
    dispatch is un-fenced (they time the enqueue, not the device),
    and dispatches issued inside a ``with self.<lock>:`` block (the
    static face of lockcheck's lock-held-across-dispatch rule).
    ``xfer.to_host`` / ``jax.device_get`` are the sanctioned exits:
    they clear taint and fence timing."""

    def __init__(self, modules, sups_by_file, findings):
        self.modules = modules
        self.sups_by_file = sups_by_file
        self.findings = findings
        self.hot_path_methods = 0

    def _flag(self, relpath, node, message):
        sups = self.sups_by_file.get(relpath, [])
        if _suppressed(sups, node.lineno, "host-sync"):
            return
        self.findings.append(Finding(
            relpath, node.lineno, "host-sync", message))

    # ---------------------------------------------------------- discovery
    def run(self, hot_modules, registry):
        marked = {}          # (relpath, name) -> (mod, fn)
        for relpath in hot_modules:
            mod = self.modules.get(relpath)
            if mod is None:
                continue
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and HOT_PATH_RE.search(
                            mod.comments.get(node.lineno, "")):
                    marked[(relpath, node.name)] = (mod, node)
        for relpath, name in registry:
            if (relpath, name) not in marked:
                self.findings.append(Finding(
                    relpath, 1, "host-sync",
                    "HOT_PATH_REGISTRY names %s.%s but no such "
                    "`# hot-path`-marked method exists — registry "
                    "drift (renamed? marker dropped?)"
                    % (relpath, name)))
        for (relpath, _name), (mod, fn) in sorted(
                marked.items(), key=lambda kv: (kv[0][0],
                                                kv[1][1].lineno)):
            self.hot_path_methods += 1
            self._analyze(mod, fn)

    # ------------------------------------------------------------ analysis
    def _analyze(self, mod, fn):
        state = {"tainted": set(), "timers": set(), "pending": False}
        self._walk_stmts(mod, fn.body, state, locks_held=0)

    @staticmethod
    def _call_kind(call):
        """'dispatch' / 'cleanser' / 'fence' / 'timing' / None."""
        name = _dotted(call.func)
        if name is None:
            if isinstance(call.func, ast.Attribute) \
                    and call.func.attr == "block_until_ready":
                return "fence"
            return None
        if name.endswith(_DISPATCH_SUFFIX) or name in _DISPATCH_NAMES:
            return "dispatch"
        if name in _CLEANSERS or name.endswith(".block_until_ready"):
            return "cleanser"
        if name in _TIMING_CALLS:
            return "timing"
        return None

    def _roots(self, expr):
        """Load-context Names in an expression."""
        return {n.id for n in ast.walk(expr)
                if isinstance(n, ast.Name)
                and isinstance(n.ctx, ast.Load)}

    def _tainted_expr(self, expr, state):
        if isinstance(expr, ast.Call):
            kind = self._call_kind(expr)
            if kind == "dispatch":
                return True
            if kind == "cleanser":
                return False
        if isinstance(expr, (ast.Name, ast.Subscript, ast.Tuple,
                             ast.Starred)):
            return bool(self._roots(expr) & state["tainted"])
        return False

    def _walk_stmts(self, mod, stmts, state, locks_held):
        for stmt in stmts:
            self._stmt(mod, stmt, state, locks_held)

    def _stmt(self, mod, stmt, state, locks_held):
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            locky = locks_held
            for item in stmt.items:
                expr = item.context_expr
                if isinstance(expr, ast.Attribute) \
                        and isinstance(expr.value, ast.Name) \
                        and expr.value.id == "self" \
                        and ("lock" in expr.attr
                             or "cond" in expr.attr):
                    locky += 1
            self._scan_exprs(mod, [stmt.items], state, locks_held)
            self._walk_stmts(mod, stmt.body, state, locky)
            return
        if isinstance(stmt, (ast.If, ast.While)):
            self._scan_node(mod, stmt.test, state, locks_held)
            self._walk_stmts(mod, stmt.body, state, locks_held)
            self._walk_stmts(mod, stmt.orelse, state, locks_held)
            return
        if isinstance(stmt, ast.For):
            self._scan_node(mod, stmt.iter, state, locks_held)
            self._walk_stmts(mod, stmt.body, state, locks_held)
            self._walk_stmts(mod, stmt.orelse, state, locks_held)
            return
        if isinstance(stmt, ast.Try):
            self._walk_stmts(mod, stmt.body, state, locks_held)
            for h in stmt.handlers:
                self._walk_stmts(mod, h.body, state, locks_held)
            self._walk_stmts(mod, stmt.orelse, state, locks_held)
            self._walk_stmts(mod, stmt.finalbody, state, locks_held)
            return
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return        # runs later, on some other thread's budget
        if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            value = stmt.value
            if value is not None:
                self._scan_node(mod, value, state, locks_held)
            targets = (stmt.targets if isinstance(stmt, ast.Assign)
                       else [stmt.target])
            names = set()
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name) \
                            and isinstance(n.ctx, ast.Store):
                        names.add(n.id)
            if value is not None and names:
                if self._tainted_expr(value, state):
                    state["tainted"] |= names
                else:
                    state["tainted"] -= names
                if isinstance(value, ast.Call) \
                        and self._call_kind(value) == "timing":
                    state["timers"] |= names
                else:
                    state["timers"] -= names
            return
        self._scan_node(mod, stmt, state, locks_held)

    def _scan_exprs(self, mod, groups, state, locks_held):
        for group in groups:
            for item in group:
                self._scan_node(mod, item.context_expr, state,
                                locks_held)

    def _scan_node(self, mod, node, state, locks_held):
        # a dispatch nested INSIDE a cleanser (`xfer.to_host(
        # self.forward(...))`) is born fenced — only bare dispatches
        # leave a program in flight
        fenced = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) \
                    and self._call_kind(sub) in ("cleanser", "fence"):
                for inner in ast.walk(sub):
                    if inner is not sub and isinstance(inner, ast.Call) \
                            and self._call_kind(inner) == "dispatch":
                        fenced.add(id(inner))
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                self._check_method_sync(mod, sub, state)
                continue
            kind = self._call_kind(sub)
            name = _dotted(sub.func) or ""
            if kind == "dispatch":
                if id(sub) not in fenced:
                    state["pending"] = True
                if locks_held:
                    self._flag(mod.relpath, sub,
                               "device dispatch %s(...) inside a "
                               "`with self.<lock>:` block — a held "
                               "lock rides the device round-trip "
                               "(lockcheck's runtime rule, statically)"
                               % name)
            elif kind in ("cleanser", "fence"):
                state["pending"] = False
            elif kind == "timing":
                pass
            elif name.startswith("jnp.") or name.startswith(
                    "jax.numpy."):
                self._flag(mod.relpath, sub,
                           "%s(...) on the hot path — implicit "
                           "host→device staging; use xfer.to_device "
                           "for dispatch arguments" % name)
            else:
                self._check_call_sync(mod, sub, name, state)
        # un-fenced timing: `time.X() - t0` while a dispatch is in
        # flight times the ENQUEUE, not the device step
        for sub in ast.walk(node):
            if isinstance(sub, ast.BinOp) \
                    and isinstance(sub.op, ast.Sub) \
                    and state["pending"]:
                ops = (sub.left, sub.right)
                has_timing = any(
                    isinstance(o, ast.Call)
                    and self._call_kind(o) == "timing" for o in ops)
                has_timer_name = any(
                    isinstance(o, ast.Name) and o.id in state["timers"]
                    for o in ops)
                if has_timing and has_timer_name:
                    self._flag(mod.relpath, sub,
                               "timing read with a dispatch in "
                               "flight — measures enqueue latency, "
                               "not device time; fence via "
                               "xfer.to_host/block_until_ready first")

    def _check_call_sync(self, mod, call, name, state):
        arg = call.args[0] if call.args else None
        if arg is None:
            return
        if isinstance(arg, ast.Call):
            # int(xfer.to_host(x)) is the SANCTIONED shape; a nested
            # dispatch (int(self._step_jit(...))) is the violation
            arg_tainted = self._call_kind(arg) == "dispatch"
        else:
            arg_tainted = (self._tainted_expr(arg, state)
                           or bool(self._roots(arg)
                                   & state["tainted"]))
        if not arg_tainted:
            return
        if name in _SYNC_BUILTINS or name in _SYNC_ASARRAY:
            self._flag(mod.relpath, call,
                       "%s(...) of a device value on the hot path — "
                       "an implicit device→host sync; route it "
                       "through xfer.to_host" % name)

    def _check_method_sync(self, mod, node, state):
        if not (isinstance(node, ast.Attribute)
                and node.attr in _SYNC_METHODS
                and isinstance(node.value, ast.Name)
                and node.value.id in state["tainted"]):
            return
        self._flag(mod.relpath, node,
                   ".%s() on a device value on the hot path — an "
                   "implicit device→host sync; route it through "
                   "xfer.to_host" % node.attr)


# ------------------------------------------------- resource-lifecycle pass
#: creation calls the escape analysis tracks when bound to a local
#: name: (kind, dotted-suffix)
_CREATORS = (
    ("future", "Future"),
    ("pages", ".alloc"),
    ("span", ".begin"),
)
#: per-kind resolver method names (called ON the tracked name, or
#: with it as first argument)
_RESOLVERS = {
    "future": frozenset(("set_result", "set_exception", "cancel")),
    "pages": frozenset(("release", "free", "release_pages",
                        "_release_pages")),
    "span": frozenset(("end",)),
}


class _LifecyclePass:
    """AST escape analysis over Future / page-alloc / tracer-span
    creation sites (ISSUE 17): a resource bound to a local name must,
    before the function ends, either ESCAPE (stored on an object,
    passed to a call, returned — ownership handed off) or RESOLVE
    (set_result/set_exception/cancel, release, end).  A site with
    neither leaks on every path (the PR 6 COW-leak class); a site
    whose only resolvers sit in straight-line code after other
    raisable calls leaks on the exception path (the PR 12
    hedge-loser-span class) unless a try/finally/except owns the
    resolution."""

    def __init__(self, modules, sups_by_file, findings):
        self.modules = modules
        self.sups_by_file = sups_by_file
        self.findings = findings
        self.lifecycle_sites = 0

    def _flag(self, relpath, node, message):
        sups = self.sups_by_file.get(relpath, [])
        if _suppressed(sups, node.lineno, "resource-lifecycle"):
            return
        self.findings.append(Finding(
            relpath, node.lineno, "resource-lifecycle", message))

    def run(self, lifecycle_modules):
        for relpath in lifecycle_modules:
            mod = self.modules.get(relpath)
            if mod is None:
                continue
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    self._function(mod, node)

    @staticmethod
    def _creation_kind(call):
        name = _dotted(call.func)
        if name is None:
            return None
        for kind, suffix in _CREATORS:
            if name == suffix.lstrip(".") or name.endswith(suffix):
                return kind
        return None

    def _function(self, mod, fn):
        creations = []       # (name, kind, node)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call):
                kind = self._creation_kind(node.value)
                if kind is not None:
                    creations.append((node.targets[0].id, kind, node))
        if not creations:
            return
        protected = self._protected_lines(fn)
        for name, kind, node in creations:
            self.lifecycle_sites += 1
            self._track(mod, fn, name, kind, node, protected)

    @staticmethod
    def _protected_lines(fn):
        """Lines inside an except handler or finally block — a
        resolver there covers the exception path."""
        lines = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Try):
                for h in node.handlers:
                    for s in h.body:
                        lines.update(range(s.lineno,
                                           (s.end_lineno or s.lineno)
                                           + 1))
                for s in node.finalbody:
                    lines.update(range(s.lineno,
                                       (s.end_lineno or s.lineno)
                                       + 1))
        return lines

    def _track(self, mod, fn, name, kind, creation, protected):
        resolvers = []       # linenos
        escapes = []         # linenos
        raisable = []        # linenos of calls that can raise
        resolver_names = _RESOLVERS[kind]
        created_at = creation.lineno
        for node in ast.walk(fn):
            line = getattr(node, "lineno", None)
            if line is None or line <= created_at or node is creation:
                continue
            if isinstance(node, ast.Call):
                func = node.func
                # resolver: name.set_result(...) / tracer.end(name)
                if isinstance(func, ast.Attribute) \
                        and func.attr in resolver_names:
                    recv = func.value
                    if isinstance(recv, ast.Name) and recv.id == name:
                        resolvers.append(line)
                        continue
                    if any(isinstance(a, ast.Name) and a.id == name
                           for a in node.args):
                        resolvers.append(line)
                        continue
                # escape: the resource handed to ANY other call
                if any(isinstance(a, ast.Name) and a.id == name
                       for sub in ast.walk(node)
                       if isinstance(sub, ast.Call)
                       for a in sub.args):
                    escapes.append(line)
                raisable.append(line)
            elif isinstance(node, ast.Assign):
                # escape: stored into an attribute/subscript/aliased
                if any(isinstance(n, ast.Name) and n.id == name
                       and isinstance(n.ctx, ast.Load)
                       for n in ast.walk(node.value)):
                    escapes.append(line)
            elif isinstance(node, (ast.Return, ast.Yield,
                                   ast.YieldFrom)):
                v = node.value
                if v is not None and any(
                        isinstance(n, ast.Name) and n.id == name
                        for n in ast.walk(v)):
                    escapes.append(line)
        if escapes:
            return           # ownership handed off — not ours to prove
        if not resolvers:
            self._flag(mod.relpath, creation,
                       "%s %r created here is never resolved "
                       "(%s) and never escapes — leaked on every "
                       "path" % (kind, name,
                                 "/".join(sorted(_RESOLVERS[kind]))))
            return
        if any(r in protected for r in resolvers):
            return           # a finally/except owns resolution
        first = min(resolvers)
        risky = [r for r in raisable
                 if created_at < r < first and r not in resolvers]
        if risky:
            self._flag(mod.relpath, creation,
                       "%s %r is resolved only in straight-line code "
                       "(first at line %d) with raisable calls in "
                       "between (line %d) — leaks on the exception "
                       "path; resolve in a finally/except"
                       % (kind, name, first, risky[0]))


# --------------------------------------------------------------- the lint
def lint_module(mod, findings):
    """Lock-discipline (classes + module globals) over one parsed
    module.  Returns per-file stats."""
    classes = guarded = external = 0
    for node in mod.tree.body:
        if isinstance(node, ast.ClassDef):
            cl = _ClassLint(mod.relpath, node, mod.comments, mod.sups,
                            findings)
            cl.run()
            classes += 1
            guarded += len(cl.guard)
            if cl.external:
                external += 1
    mg = _ModuleGlobalsLint(mod.relpath, mod.tree, mod.comments,
                            mod.sups, findings)
    mg.run()
    return {"classes": classes, "guarded_attrs": guarded,
            "external": external,
            "module_globals": len(mg.guard)}


def run_check(root=REPO, modules=SERVING_MODULES,
              purity_modules=PURITY_MODULES, registry=TRACED_REGISTRY,
              census_modules=CENSUS_MODULES,
              jit_guard_fixtures=JIT_GUARD_FIXTURES,
              hot_path_registry=HOT_PATH_REGISTRY,
              lifecycle_modules=None):
    """The full-tree check, all passes over ONE shared parse per
    module: lock discipline, traced purity, recompile hazards (+ the
    program-family census cross-check), host-sync taint over hot-path
    methods, resource-lifecycle escape analysis, and suppression
    hygiene.  ``lifecycle_modules`` defaults to ``modules``.  Returns
    (findings, suppressions, stats)."""
    import time as _time
    t0 = _time.perf_counter()
    findings, suppressions = [], []
    stats = {"files": 0, "classes": 0, "guarded_attrs": 0,
             "module_globals": 0, "external": 0}
    if lifecycle_modules is None:
        lifecycle_modules = modules
    mset = _ModuleSet(root)
    sups_by_file = {}

    def _adopt(relpath):
        """Register a module's suppressions (once per file)."""
        mod = mset.get(relpath)
        if mod is None or relpath in sups_by_file:
            return mod
        findings.extend(mod.sup_findings)
        suppressions.extend(mod.sups)
        sups_by_file[relpath] = mod.sups
        return mod

    for relpath in modules:
        mod = _adopt(relpath)
        if mod is None:
            continue
        st = lint_module(mod, findings)
        stats["files"] += 1
        for k in ("classes", "guarded_attrs", "module_globals",
                  "external"):
            stats[k] += st[k]
    # every file ANY pass reads contributes its suppressions, so an
    # allow() in a purity/census/fixture file is honored and audited
    for relpath in (tuple(purity_modules)
                    + tuple(r for r, _ in registry)
                    + tuple(census_modules)
                    + tuple(jit_guard_fixtures)
                    + tuple(lifecycle_modules)):
        _adopt(relpath)
    purity = _PurityPass(mset, sups_by_file, findings)
    purity.run(purity_modules, registry)
    stats["traced_functions"] = purity.traced_functions
    # ... and so does every file the purity pass FOLLOWED a call into
    # (``ops/latent.py`` calls ``ops/attention.py::paged_write``): the
    # recompile pass flags lines there, so an allow() there must count
    for mod, _ in purity.analyzed:
        _adopt(mod.relpath)
    recompile = _RecompilePass(mset, sups_by_file, findings)
    recompile.run_bodies(purity.analyzed)
    recompile.run_census(census_modules, jit_guard_fixtures)
    stats["census_sites"] = recompile.census_sites
    hostsync = _HostSyncPass(mset, sups_by_file, findings)
    hostsync.run(modules, hot_path_registry)
    stats["hot_path_methods"] = hostsync.hot_path_methods
    lifecycle = _LifecyclePass(mset, sups_by_file, findings)
    lifecycle.run(lifecycle_modules)
    stats["lifecycle_sites"] = lifecycle.lifecycle_sites
    for s in suppressions:
        if not s.used:
            findings.append(Finding(
                s.file, s.line, "suppression",
                "suppression (%s) matched no finding — stale "
                "exception, delete it" % s.check))
    stats["suppressions"] = len(suppressions)
    stats["parses"] = mset.parses()
    stats["wall_s"] = round(_time.perf_counter() - t0, 3)
    findings.sort(key=lambda f: (f.file, f.line))
    return findings, suppressions, stats


# ------------------------------------------------------------- record/CLI
def summary_record(results):
    """The bench.py-shaped streamed summary record (validated by
    tools/check_stream_records.py builtin mode)."""
    stats = results.get("stats", {}) if isinstance(results, dict) else {}
    n = results.get("findings") if isinstance(results, dict) else None
    return [{
        "metric": "lint_findings",
        "value": int(n) if n is not None else 0,
        "unit": "count",
        "vs_baseline": "0 on a clean tree (ISSUE 15/17 acceptance)",
        "configs": {
            "files": stats.get("files", 0),
            "classes": stats.get("classes", 0),
            "guarded_attrs": stats.get("guarded_attrs", 0),
            "module_globals": stats.get("module_globals", 0),
            "traced_functions": stats.get("traced_functions", 0),
            "census_sites": stats.get("census_sites", 0),
            "hot_path_methods": stats.get("hot_path_methods", 0),
            "lifecycle_sites": stats.get("lifecycle_sites", 0),
            "suppressions": stats.get("suppressions", 0),
            "parses": stats.get("parses", 0),
            "wall_s": stats.get("wall_s", 0.0),
        },
    }]


def clean_record(findings, stats):
    """The bench-leg ``lint_clean`` assertion record (ISSUE 17
    satellite): lm_bench/chaos_bench run the full check as one leg
    and stream this — 1 means the shipped tree is lint-clean.  Takes
    a findings count or list."""
    n = findings if isinstance(findings, int) else len(findings)
    stats = stats or {}
    return [{
        "metric": "lint_clean",
        "value": 0 if n else 1,
        "unit": "bool",
        "vs_baseline": "1 (zero findings) on a shipped tree",
        "configs": {
            "findings": int(n),
            "files": stats.get("files", 0),
            "traced_functions": stats.get("traced_functions", 0),
            "hot_path_methods": stats.get("hot_path_methods", 0),
            "census_sites": stats.get("census_sites", 0),
            "lifecycle_sites": stats.get("lifecycle_sites", 0),
            "suppressions": stats.get("suppressions", 0),
            "wall_s": stats.get("wall_s", 0.0),
        },
    }]


def exit_code(findings):
    """OR of PASS_BITS for every pass with >= 1 finding — CI reads
    WHICH passes failed from the status alone (0 = clean)."""
    code = 0
    for f in findings:
        code |= PASS_BITS.get(f.check, 64)
    return code


def main(argv=None):
    parser = argparse.ArgumentParser(
        description=__doc__.split("\n")[0])
    parser.add_argument("--check", action="store_true",
                        help="run the full-tree check (the default)")
    parser.add_argument("--all", action="store_true",
                        help="alias for --check: every pass — lock "
                             "discipline, traced purity, recompile "
                             "hazard, host sync, resource lifecycle, "
                             "suppression hygiene")
    parser.add_argument("--root", default=REPO,
                        help="repository root (default: this repo)")
    parser.add_argument("--list-suppressions", action="store_true",
                        help="print every named suppression and exit")
    args = parser.parse_args(argv)
    findings, suppressions, stats = run_check(args.root)
    if args.list_suppressions:
        for s in suppressions:
            print("%s:%d: allow(%s): %s"
                  % (s.file, s.line, s.check, s.reason))
        return 0
    for f in findings:
        print("%s:%d: [%s] %s" % (f.file, f.line, f.check, f.message),
              file=sys.stderr)
    results = {"findings": len(findings), "stats": stats}
    print(json.dumps(summary_record(results)[0]))
    return exit_code(findings)


if __name__ == "__main__":
    sys.exit(main())
