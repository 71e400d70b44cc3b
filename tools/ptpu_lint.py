"""Repo-invariant linter (docs/STATIC_ANALYSIS.md) — the source-level
sibling of the Program IR verifier: AST checks for the conventions the
framework relies on but Python cannot enforce.

Rules:

  env-read     every `PTPU_*` environment read must go through the
               central `paddle_tpu.flags` registry (`flags.env(...)`),
               never `os.environ[...]`/`os.environ.get`/`os.getenv`
               directly — the registry is what pins type, default and
               boolean spelling (the `_env_flag` drift class of bug)
  env-undeclared
               a flag name passed to `flags.env("PTPU_...")` (or
               `env_flag`) must exist in the registry — a typo'd name
               fails here instead of silently reading a default
  bare-except  no `except:` without an exception type — it swallows
               KeyboardInterrupt/SystemExit and masks real faults
  buildtime-jnp
               an op-BUILDER function (one that calls `append_op`/
               `prepend_op`, i.e. runs at program-build time) in
               `layers/` or `ops/` must not also call `jnp.*`/`jax.*` —
               that executes device compute while building the graph
               (kernels run jnp at TRACE time; builders must not)
  metric-undocumented
               a metric name literal passed to `counter()/gauge()/
               histogram()` must appear in docs/OBSERVABILITY.md — the
               registry's exposition tables are the contract dashboards
               are built against
  event-undocumented
               a flight-recorder event-type literal passed to
               `record_event()` must appear in docs/OBSERVABILITY.md —
               the crash-dump schema is the contract post-mortem
               tooling greps against (mirrors metric-undocumented)
  flag-undocumented
               every `PTPU_*` flag declared in the paddle_tpu.flags
               registry must appear somewhere under docs/ (or the
               README) — a flag nobody can discover is a flag nobody
               can audit; the registry docstring alone is not
               documentation (mirrors metric-undocumented, but checked
               registry-side rather than call-site)
  fault-site-literal
               fault-injection site literals must parse under the
               registered injector grammar (FaultInjector's
               STEP_SITES/OCCURRENCE_SITES, loaded from resilience.py
               BY AST): a site name passed to `fire_at_step`/
               `fire_occurrence` must be registered in the matching
               category (a typo'd site there silently never fires —
               the hook just finds nothing armed), and any spec string
               bound to the `PTPU_FAULT_INJECT` env key (setenv /
               os.environ assignment / env-dict literal or keyword)
               must parse as comma-separated `site:N` pairs.
               `FaultInjector(...)` constructor literals are exempt:
               the constructor validates its spec loudly itself (and
               tests deliberately hand it garbage to pin that)

Concurrency rules (docs/STATIC_ANALYSIS.md "Concurrency analysis" —
receivers are judged by NAME: `lock`/`mu`/`mutex` and `*_lock`-style
names are lock-like, `cv`/`cond`/`condition` and `*_cv`-style names are
condition-like; the runtime keeps to those spellings so the rules stay
sound):

  lock-with    a lock-like receiver's bare `.acquire()` must be paired
               with a try/finally that releases the same receiver in
               the enclosing scope — otherwise use `with` (an exception
               between acquire and release orphans the lock forever);
               non-blocking probes (`acquire(False)` / `timeout=`) and
               delegating wrappers (an enclosing function itself named
               `acquire`/`__enter__`) are exempt
  cond-wait-loop
               a condition-like receiver's `.wait()` must sit inside a
               `while` loop — `if pred: cv.wait()` is spurious-wakeup-
               unsafe (PEP 343 era condition contract); `.wait_for()`
               builds the loop in and is exempt, as are delegating
               wrappers (an enclosing function itself named `wait`/
               `wait_for`)
  thread-lifecycle
               every `threading.Thread(...)` is `daemon=True` (at the
               constructor or via `.daemon = True` in the same scope —
               a literal False earns no credit) or provably joined (a
               `.join()` on a name the scope binds a Thread to; a stray
               str.join/queue.join cannot vouch) — a forgotten
               non-daemon thread hangs interpreter exit
  sleep-under-lock
               no `time.sleep(...)` lexically inside a `with <lock-like>`
               block — sleeping under a lock serializes every waiter
               behind the nap

Usage:
  python tools/ptpu_lint.py [path ...]     # default: paddle_tpu/
  python tools/ptpu_lint.py --list-rules

Exit status 1 when any finding is reported (the CI `lint` stage gates on
zero findings).
"""

import argparse
import ast
import importlib.util
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLAGS_PATH = os.path.join(REPO_ROOT, "paddle_tpu", "flags.py")
RESILIENCE_PATH = os.path.join(REPO_ROOT, "paddle_tpu", "resilience.py")
OBS_DOC_PATH = os.path.join(REPO_ROOT, "docs", "OBSERVABILITY.md")
STATIC_DOC_PATH = os.path.join(REPO_ROOT, "docs", "STATIC_ANALYSIS.md")

RULES = {
    "env-read": "PTPU_* environment reads must go through flags.env",
    "env-undeclared": "flag names passed to flags.env/env_flag must be "
                      "declared in the registry",
    "bare-except": "no bare `except:` handlers",
    "buildtime-jnp": "op-builder functions may not call jnp.*/jax.* at "
                     "program-build time",
    "metric-undocumented": "metric name literals must appear in "
                           "docs/OBSERVABILITY.md",
    "event-undocumented": "flight-recorder event-type literals must "
                          "appear in docs/OBSERVABILITY.md",
    "flag-undocumented": "every registry-declared PTPU_* flag must "
                         "appear in docs/ (or the README)",
    "fault-site-literal": "fault-injection site literals must parse "
                          "under the registered injector grammar "
                          "(a typo'd site silently never fires)",
    "lock-with": "lock-like receivers are acquired via `with` (or "
                 "try/finally-released); no orphanable bare .acquire()",
    "cond-wait-loop": "condition-like .wait() must sit in a `while` "
                      "loop (spurious wakeups); .wait_for is exempt",
    "thread-lifecycle": "every threading.Thread is daemon=True or "
                        "provably joined in the same scope",
    "sleep-under-lock": "no time.sleep inside a `with <lock>` block",
}

# receiver-name heuristics for the concurrency rules: the runtime names
# its primitives this way on purpose (docs/STATIC_ANALYSIS.md)
_LOCKISH = re.compile(r"_{0,2}(?:.*_)?(?:lock|mu|mutex|cv|cond|condition)$")
_CONDISH = re.compile(r"_{0,2}(?:.*_)?(?:cv|cond|condition)$")


def _recv_name(node):
    """Terminal name of a receiver expression: `self._cv` -> '_cv',
    `lock` -> 'lock', anything else -> None."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _is_lockish(node):
    name = _recv_name(node)
    return name is not None and bool(_LOCKISH.fullmatch(name.lower()))


def _is_condish(node):
    name = _recv_name(node)
    return name is not None and bool(_CONDISH.fullmatch(name.lower()))

# directories whose functions are program-BUILDERS when they append ops
_BUILDER_DIRS = (os.path.join("paddle_tpu", "layers"),
                 os.path.join("paddle_tpu", "ops"))

_ENV_CALL_NAMES = ("env", "env_flag", "flags_env", "_env", "_env_flag",
                   "_env_on")


class Finding:
    __slots__ = ("path", "line", "rule", "message")

    def __init__(self, path, line, rule, message):
        self.path = path
        self.line = line
        self.rule = rule
        self.message = message

    def __str__(self):
        rel = os.path.relpath(self.path, REPO_ROOT)
        return "%s:%d: [%s] %s" % (rel, self.line, self.rule,
                                   self.message)


def declared_flag_names():
    """Flag names from the registry, loaded from flags.py BY PATH — the
    module is stdlib-only, so the linter never imports the jax-heavy
    package."""
    spec = importlib.util.spec_from_file_location("_ptpu_flags",
                                                  FLAGS_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return set(mod.declared_flags())


_SITES_CACHE = {}


def injector_sites(path=RESILIENCE_PATH):
    """(step_sites, occurrence_sites) of the registered FaultInjector
    grammar, read from resilience.py BY AST — the module imports jax-
    heavy packages, and the linter must never import the tree it
    lints. Returns frozensets; empty when the class cannot be found
    (the rule then reports nothing rather than everything)."""
    if path in _SITES_CACHE:
        return _SITES_CACHE[path]
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return frozenset(), frozenset()
    step, occ = frozenset(), frozenset()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ClassDef)
                and node.name == "FaultInjector"):
            continue
        for stmt in node.body:
            if not isinstance(stmt, ast.Assign):
                continue
            names = {t.id for t in stmt.targets
                     if isinstance(t, ast.Name)}
            if not isinstance(stmt.value, ast.Tuple):
                continue
            vals = frozenset(
                e.value for e in stmt.value.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, str))
            if "STEP_SITES" in names:
                step = vals
            elif "OCCURRENCE_SITES" in names:
                occ = vals
    _SITES_CACHE[path] = (step, occ)
    return step, occ


def fault_spec_problems(spec, step_sites, occurrence_sites):
    """Problems with one PTPU_FAULT_INJECT-style spec literal under the
    registered grammar (comma/semicolon-separated `site:N`, dashes
    normalized like FaultInjector does). Empty list = parses clean."""
    known = step_sites | occurrence_sites
    problems = []
    for part in (spec or "").replace(";", ",").split(","):
        part = part.strip()
        if not part:
            continue
        site, _, num = part.partition(":")
        site = site.strip().replace("-", "_")
        if site not in known:
            problems.append("unknown site %r" % site)
            continue
        try:
            int(num)
        except ValueError:
            problems.append("%r wants site:N" % part)
    return problems


def documented_metric_names():
    """The raw OBSERVABILITY.md text; documented-name checks are
    substring membership (table rows list several names per cell)."""
    try:
        with open(OBS_DOC_PATH) as f:
            obs = f.read()
    except OSError:
        obs = ""
    try:
        with open(STATIC_DOC_PATH) as f:
            obs += f.read()
    except OSError:
        pass
    return obs


def documented_flag_corpus():
    """Every docs/*.md file plus the README, concatenated — the text a
    registry-declared flag name must appear in (the flag-undocumented
    rule). Broader than the metric corpus on purpose: each subsystem
    documents its own flags in its own doc."""
    corpus = []
    docs_dir = os.path.join(REPO_ROOT, "docs")
    try:
        names = sorted(os.listdir(docs_dir))
    except OSError:
        names = []
    for name in names:
        if name.endswith(".md"):
            try:
                with open(os.path.join(docs_dir, name)) as f:
                    corpus.append(f.read())
            except OSError:
                pass
    try:
        with open(os.path.join(REPO_ROOT, "README.md")) as f:
            corpus.append(f.read())
    except OSError:
        pass
    return "\n".join(corpus)


def flag_doc_findings(flag_names=None, corpus=None):
    """The flag-undocumented rule: one finding per registry-declared
    PTPU_* flag that appears nowhere in the docs corpus. Checked once
    per lint run (registry-side), anchored at the flag's declaration
    line in flags.py. ``flag_names``/``corpus`` are injectable for the
    fixture tests; defaults read the real registry and docs/."""
    if flag_names is None:
        flag_names = declared_flag_names()
    if corpus is None:
        corpus = documented_flag_corpus()
    try:
        with open(FLAGS_PATH) as f:
            src_lines = f.read().splitlines()
    except OSError:
        src_lines = []
    findings = []
    for name in sorted(flag_names):
        # word-boundary match: a flag whose name prefixes another
        # documented flag (PTPU_QUANT vs PTPU_QUANT_MODE) must not be
        # vouched for by the longer name's mentions
        if re.search(r"\b%s\b" % re.escape(name), corpus):
            continue
        line = next((i + 1 for i, s in enumerate(src_lines)
                     if '"%s"' % name in s or "'%s'" % name in s), 0)
        findings.append(Finding(
            FLAGS_PATH, line, "flag-undocumented",
            "flag %s is declared in the paddle_tpu.flags registry but "
            "documented nowhere under docs/ (or the README)" % name))
    return findings


def _is_environ(node):
    """node is `os.environ` (or bare `environ` from `from os import
    environ`)."""
    if isinstance(node, ast.Attribute) and node.attr == "environ" \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "os":
        return True
    return isinstance(node, ast.Name) and node.id == "environ"


def _const_str(node):
    return node.value if isinstance(node, ast.Constant) \
        and isinstance(node.value, str) else None


class _Linter(ast.NodeVisitor):
    def __init__(self, path, flag_names, doc_text, is_flags_module,
                 builder_scope, sites=None):
        self.path = path
        self.flag_names = flag_names
        self.doc_text = doc_text
        self.is_flags_module = is_flags_module
        self.builder_scope = builder_scope
        self.step_sites, self.occurrence_sites = (
            sites if sites is not None else injector_sites())
        self.findings = []
        self._func_stack = []

    def _add(self, node, rule, message):
        self.findings.append(Finding(self.path, node.lineno, rule,
                                     message))

    # -- helpers -------------------------------------------------------
    def _check_env_name_arg(self, node):
        """`flags.env("NAME")`-family call: NAME must be declared."""
        if not node.args:
            return
        name = _const_str(node.args[0])
        if name is not None and name.startswith("PTPU_") \
                and name not in self.flag_names:
            self._add(node, "env-undeclared",
                      "flag %r is not declared in the paddle_tpu.flags "
                      "registry" % name)

    def _ptpu_arg(self, node):
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            s = _const_str(arg)
            if s is not None and s.startswith("PTPU_"):
                return s
        return None

    def _check_fault_spec(self, node, spec):
        """A spec literal bound to the PTPU_FAULT_INJECT env key must
        parse under the registered grammar."""
        if spec is None or not (self.step_sites
                                or self.occurrence_sites):
            return
        for problem in fault_spec_problems(spec, self.step_sites,
                                           self.occurrence_sites):
            self._add(node, "fault-site-literal",
                      "PTPU_FAULT_INJECT spec %r: %s — registered "
                      "sites: %s" % (spec, problem, ", ".join(
                          sorted(self.step_sites
                                 | self.occurrence_sites))))

    def _check_fire_site(self, node, kind):
        """`fire_at_step("site", ...)` / `fire_occurrence("site")`:
        an unregistered literal silently never fires (the hook finds
        nothing armed) — exactly the bug class this rule exists for.
        The keyword spelling (`fire_at_step(site="...", ...)`) is
        checked too."""
        if not (self.step_sites or self.occurrence_sites):
            return
        site_arg = node.args[0] if node.args else next(
            (kw.value for kw in node.keywords if kw.arg == "site"),
            None)
        site = _const_str(site_arg) if site_arg is not None else None
        if site is None:
            return
        want = (self.step_sites if kind == "fire_at_step"
                else self.occurrence_sites)
        other = (self.occurrence_sites if kind == "fire_at_step"
                 else self.step_sites)
        if site in want:
            return
        if site in other:
            self._add(node, "fault-site-literal",
                      "site %r is registered for %s, not %s — this "
                      "call can never fire" % (
                          site,
                          "occurrence keying" if kind == "fire_at_step"
                          else "step keying", kind))
        else:
            self._add(node, "fault-site-literal",
                      "site %r is not registered in FaultInjector's "
                      "grammar — %s silently never fires (registered: "
                      "%s)" % (site, kind,
                               ", ".join(sorted(want))))

    # -- visitors ------------------------------------------------------
    def visit_FunctionDef(self, node):
        self._func_stack.append({"appends": False, "jnp_calls": []})
        self.generic_visit(node)
        info = self._func_stack.pop()
        if self.builder_scope and info["appends"]:
            for call in info["jnp_calls"]:
                self._add(call, "buildtime-jnp",
                          "op-builder %r calls %s at program-build time "
                          "— compute belongs in the op KERNEL, not the "
                          "builder" % (node.name, call._jnp_repr))

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ExceptHandler(self, node):
        if node.type is None:
            self._add(node, "bare-except",
                      "bare `except:` swallows KeyboardInterrupt/"
                      "SystemExit — name the exception class")
        self.generic_visit(node)

    def visit_Subscript(self, node):
        if not self.is_flags_module and _is_environ(node.value) \
                and isinstance(node.ctx, ast.Load):
            key = _const_str(node.slice)
            if key is not None and key.startswith("PTPU_"):
                self._add(node, "env-read",
                          "read %s through flags.env(%r), not "
                          "os.environ" % (key, key))
        self.generic_visit(node)

    def visit_Assign(self, node):
        # os.environ["PTPU_FAULT_INJECT"] = "<spec>"
        for t in node.targets:
            if isinstance(t, ast.Subscript) and _is_environ(t.value) \
                    and _const_str(t.slice) == "PTPU_FAULT_INJECT":
                self._check_fault_spec(node, _const_str(node.value))
        self.generic_visit(node)

    def visit_Dict(self, node):
        # {"PTPU_FAULT_INJECT": "<spec>", ...} (subprocess env dicts)
        for k, v in zip(node.keys, node.values):
            if k is not None and _const_str(k) == "PTPU_FAULT_INJECT":
                self._check_fault_spec(node, _const_str(v))
        self.generic_visit(node)

    def visit_Call(self, node):
        func = node.func
        # os.environ.get("PTPU_...") / os.getenv("PTPU_...")
        if isinstance(func, ast.Attribute):
            if func.attr == "get" and _is_environ(func.value) \
                    and not self.is_flags_module:
                key = self._ptpu_arg(node)
                if key:
                    self._add(node, "env-read",
                              "read %s through flags.env(%r), not "
                              "os.environ.get" % (key, key))
            elif func.attr == "getenv" \
                    and isinstance(func.value, ast.Name) \
                    and func.value.id == "os" \
                    and not self.is_flags_module:
                key = self._ptpu_arg(node)
                if key:
                    self._add(node, "env-read",
                              "read %s through flags.env(%r), not "
                              "os.getenv" % (key, key))
            elif func.attr in _ENV_CALL_NAMES:
                self._check_env_name_arg(node)
            elif func.attr in ("fire_at_step", "fire_occurrence"):
                self._check_fire_site(node, func.attr)
            elif func.attr == "setenv" and len(node.args) >= 2 \
                    and _const_str(node.args[0]) == "PTPU_FAULT_INJECT":
                self._check_fault_spec(node, _const_str(node.args[1]))
            # metric name literals: counter/gauge/histogram/samples("a/b")
            if func.attr in ("counter", "gauge", "histogram",
                             "samples") and node.args:
                name = _const_str(node.args[0])
                if name and "/" in name and name not in self.doc_text:
                    self._add(node, "metric-undocumented",
                              "metric %r is not documented in "
                              "docs/OBSERVABILITY.md" % name)
            # flight-recorder event-type literals: record_event("etype")
            # — the crash-dump schema is the contract post-mortem
            # tooling greps against, same deal as the metric tables
            if func.attr == "record_event" and node.args:
                etype = _const_str(node.args[0])
                if etype and etype not in self.doc_text:
                    self._add(node, "event-undocumented",
                              "flight-recorder event %r is not "
                              "documented in docs/OBSERVABILITY.md"
                              % etype)
            # builder-scope jnp/jax calls
            root = func
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id in ("jnp", "jax") \
                    and self._func_stack:
                node._jnp_repr = ast.unparse(func) if hasattr(
                    ast, "unparse") else root.id + ".*"
                self._func_stack[-1]["jnp_calls"].append(node)
            if func.attr in ("append_op", "prepend_op") \
                    and self._func_stack:
                self._func_stack[-1]["appends"] = True
        elif isinstance(func, ast.Name):
            if func.id in _ENV_CALL_NAMES:
                self._check_env_name_arg(node)
        # PTPU_FAULT_INJECT="<spec>" keyword (dict(...)-built env maps)
        for kw in node.keywords:
            if kw.arg == "PTPU_FAULT_INJECT":
                self._check_fault_spec(node, _const_str(kw.value))
        self.generic_visit(node)


def _parent_map(tree):
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _ancestors(node, parents):
    n = parents.get(node)
    while n is not None:
        yield n
        n = parents.get(n)


_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _enclosing_scope(node, parents):
    """Nearest enclosing function (or the module) — the unit the
    thread-lifecycle/daemon-assignment scan runs over."""
    for a in _ancestors(node, parents):
        if isinstance(a, _SCOPES + (ast.Module,)):
            return a
    return None


def _nonblocking_acquire(call):
    """acquire(False) / acquire(blocking=False) / any timeout= probe —
    the caller is inspecting, not holding-forever-on-raise."""
    if call.args:
        a0 = call.args[0]
        if isinstance(a0, ast.Constant) and a0.value is False:
            return True
        if len(call.args) > 1:
            return True  # positional timeout
    for kw in call.keywords:
        if kw.arg == "timeout":
            return True
        if kw.arg == "blocking" and isinstance(kw.value, ast.Constant) \
                and kw.value.value is False:
            return True
    return False


def _try_releases(try_node, recv_name=None):
    """The Try's finalbody contains a `.release()` call (on `recv_name`
    when given)."""
    for stmt in try_node.finalbody:
        for n in ast.walk(stmt):
            if isinstance(n, ast.Call) \
                    and isinstance(n.func, ast.Attribute) \
                    and n.func.attr == "release" \
                    and (recv_name is None
                         or _recv_name(n.func.value) == recv_name):
                return True
    return False


def _scope_finally_releases(scope, recv_name):
    """The enclosing scope holds a try/finally releasing `recv_name` —
    covers the canonical `lock.acquire()`-BEFORE-`try` idiom (the
    acquire must not sit inside the try, else a failed acquire would
    release a lock it never took)."""
    for n in ast.walk(scope):
        if isinstance(n, ast.Try) and _try_releases(n, recv_name):
            return True
    return False


def _concurrency_findings(tree, path):
    """The four concurrency rules (lock-with, cond-wait-loop,
    thread-lifecycle, sleep-under-lock) — parent-map based, since they
    reason about statement CONTEXT rather than call shape."""
    parents = _parent_map(tree)
    findings = []

    def add(node, rule, message):
        findings.append(Finding(path, node.lineno, rule, message))

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func

        # -- lock-with -------------------------------------------------
        if isinstance(func, ast.Attribute) and func.attr == "acquire" \
                and _is_lockish(func.value) \
                and not _nonblocking_acquire(node):
            scope = _enclosing_scope(node, parents)
            wrapper = isinstance(scope, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                and scope.name in ("acquire", "__enter__")
            if not wrapper and not _scope_finally_releases(
                    scope or tree, _recv_name(func.value)):
                add(node, "lock-with",
                    "bare %s.acquire() without a try/finally release — "
                    "acquire via `with` so an exception cannot orphan "
                    "the lock" % _recv_name(func.value))

        # -- cond-wait-loop --------------------------------------------
        if isinstance(func, ast.Attribute) and func.attr == "wait" \
                and _is_condish(func.value):
            scope = _enclosing_scope(node, parents)
            wrapper = isinstance(scope, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                and scope.name in ("wait", "wait_for")
            in_while = False
            for a in _ancestors(node, parents):
                if isinstance(a, ast.While):
                    in_while = True
                    break
                if isinstance(a, _SCOPES):
                    break  # don't credit a loop outside this function
            if not in_while and not wrapper:
                add(node, "cond-wait-loop",
                    "%s.wait() outside a `while` loop — an `if`-guarded "
                    "wait is spurious-wakeup-unsafe; loop on the "
                    "predicate (or use wait_for)"
                    % _recv_name(func.value))

        # -- thread-lifecycle ------------------------------------------
        is_thread = (isinstance(func, ast.Attribute)
                     and func.attr == "Thread"
                     and isinstance(func.value, ast.Name)
                     and func.value.id == "threading") \
            or (isinstance(func, ast.Name) and func.id == "Thread")
        if is_thread:
            # daemon=<anything but a literal False> at the constructor
            # satisfies the rule; an explicit daemon=False is exactly
            # the non-daemon thread the rule exists to catch and gets
            # no credit (it still passes with a join in scope)
            daemonized = any(
                kw.arg == "daemon"
                and not (isinstance(kw.value, ast.Constant)
                         and kw.value.value is False)
                for kw in node.keywords)
            if not daemonized:
                scope = _enclosing_scope(node, parents) or tree
                # names THIS Thread call is bound to (its parent
                # Assign's targets): only a `.daemon = True` or
                # `.join()` on one of these counts — an unrelated
                # object's daemon flag, another thread's join, or a
                # stray str.join/queue.join must not vouch for it (and
                # a chained `Thread(...).start()` binds no name, so
                # nothing can)
                bound = set()
                parent = parents.get(node)
                if isinstance(parent, ast.Assign):
                    for t in parent.targets:
                        name = _recv_name(t)
                        if name is not None:
                            bound.add(name)
                owned = False
                for n in ast.walk(scope):
                    if isinstance(n, ast.Assign) and any(
                            isinstance(t, ast.Attribute)
                            and t.attr == "daemon"
                            and _recv_name(t.value) in bound
                            for t in n.targets) \
                            and not (isinstance(n.value, ast.Constant)
                                     and n.value.value is False):
                        owned = True
                        break
                    if isinstance(n, ast.Call) \
                            and isinstance(n.func, ast.Attribute) \
                            and n.func.attr == "join" \
                            and _recv_name(n.func.value) in bound:
                        owned = True
                        break
                if not owned:
                    add(node, "thread-lifecycle",
                        "threading.Thread without daemon=True and no "
                        "visible join in this scope — a forgotten "
                        "non-daemon thread hangs interpreter exit; mark "
                        "it daemon or own a close()/join() path")

        # -- sleep-under-lock ------------------------------------------
        if isinstance(func, ast.Attribute) and func.attr == "sleep":
            root = func.value
            if isinstance(root, ast.Name) and root.id in ("time",
                                                          "_time"):
                for a in _ancestors(node, parents):
                    if isinstance(a, _SCOPES):
                        break  # deferred body: not under the with
                    if isinstance(a, ast.With) and any(
                            _is_lockish(item.context_expr)
                            for item in a.items):
                        add(node, "sleep-under-lock",
                            "time.sleep while holding %s — every waiter "
                            "on that lock sleeps too; sleep outside the "
                            "critical section"
                            % ", ".join(
                                _recv_name(item.context_expr) or "a lock"
                                for item in a.items
                                if _is_lockish(item.context_expr)))
                        break
    return findings


def lint_file(path, flag_names, doc_text, sites=None):
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, "parse-error", str(e))]
    norm = os.path.abspath(path).replace(os.sep, "/")
    is_flags = os.path.abspath(path) == FLAGS_PATH
    builder = any(("/%s/" % d.replace(os.sep, "/")) in norm
                  for d in _BUILDER_DIRS)
    linter = _Linter(path, flag_names, doc_text, is_flags, builder,
                     sites=sites)
    linter.visit(tree)
    return linter.findings + _concurrency_findings(tree, path)


def iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p):
            yield p
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("paths", nargs="*",
                    default=[os.path.join(REPO_ROOT, "paddle_tpu")],
                    help="files/directories to lint (default: "
                         "paddle_tpu/)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)
    if args.list_rules:
        for rule in sorted(RULES):
            print("%-20s %s" % (rule, RULES[rule]))
        return 0
    flag_names = declared_flag_names()
    doc_text = documented_metric_names()
    findings = []
    n_files = 0
    for path in iter_py_files(args.paths):
        n_files += 1
        findings.extend(lint_file(path, flag_names, doc_text))
    # registry-side rule: once per run, not per file
    findings.extend(flag_doc_findings(flag_names))
    for f in findings:
        print(f)
    print("ptpu_lint: %d file(s), %d finding(s)" % (n_files,
                                                    len(findings)),
          file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
