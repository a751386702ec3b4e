"""Layer 1 of `jagcheck`: the repo-specific AST lint (rules JAG001–JAG006).

Each rule mechanizes an invariant a past PR established and a later change
could silently break:

  JAG001  no ``jax.jit`` outside ``serve/executor.py``, ``core/build.py``
          and the ``launch/`` paths — PR 2's "zero jit blocks in
          core/jag.py" contract, generalized: every serving compilation
          must go through the Executor's one epoch-keyed cache so compiled
          variants stay enumerable and evictable.
  JAG002  no batch-variant ``einsum("bcd,bd->bc", ...)`` candidate dots —
          PR 3's bit-identity contract: a batched-dot lowering picks
          different reduction vectorization per batch size, so per-query
          regrouping would leak group composition into a query's low-order
          float bits. Use ``distances.gathered_dot``.
  JAG003  no module-level ``functools.lru_cache``/``cache`` — the PR 3
          ``sample_ids`` bug class: a module-level memo capturing device
          buffers pins them process-wide across index lifetimes. Cache on
          the owning object instead.
  JAG004  executor-cache key hygiene: any ``*._cache[...]`` insertion must
          include an epoch component in its key expression — PR 4's
          stale-probe bug class: epoch-less keys serve pre-insert
          compilations after the index grows.
  JAG005  no ``np.asarray`` / ``.item()`` / ``float(x)`` host syncs inside
          functions traced by ``jax.jit`` (decorated, lexically wrapped,
          or returned by an executor ``make()`` factory).
  JAG006  no telemetry host work inside jit-traced functions — PR 9's
          observability contract: ``time.*`` timestamps constant-fold at
          trace time (a compiled route would report its tracing wall
          clock forever), and telemetry-object mutations (ring-buffer
          ``append``, histogram ``observe``, counter ``inc``, trace
          ``record*``) are host state that must only be touched AFTER the
          route returns, in the dispatch/search_auto wrappers. A span
          (``span(...)``, ``recorder.span(...)``, ``TraceAnnotation(...)``)
          inside a traced function would time tracing, not execution:
          spans go around the compiled call.

Diagnostics are ``path:line: CODE message``. The config and allowlist live
in ``pyproject.toml`` under ``[tool.jagcheck]``; every allowlist entry
needs a non-empty ``reason`` (the one-line justification the satellite
contract requires) and entries that no longer match any finding are
themselves reported (stale suppressions hide future regressions).

Scanning is purely syntactic and per-file: a rule sees the AST of one
module at a time (no cross-module call-graph), which is exactly the level
the original bugs were visible at.
"""
from __future__ import annotations

import ast
import dataclasses
import fnmatch
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

RULES = {
    "JAG001": "jax.jit outside the executor/build/launch jit surface",
    "JAG002": "batch-variant einsum candidate dot (use distances.gathered_dot)",
    "JAG003": "module-level lru_cache can pin device buffers process-wide",
    "JAG004": "cache insertion key lacks an epoch component",
    "JAG005": "host sync inside a jit-traced function",
    "JAG006": "telemetry host work inside a jit-traced function",
    # meta-diagnostics about the allowlist itself
    "JAGCFG": "jagcheck configuration problem",
}

_EINSUM_SPEC = "bcd,bd->bc"


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # posix path relative to the repo root
    line: int
    msg: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.msg}"


@dataclasses.dataclass(frozen=True)
class AllowEntry:
    rule: str
    path: str          # fnmatch glob over the relative posix path
    reason: str


@dataclasses.dataclass
class LintConfig:
    include: Tuple[str, ...] = ("src/repro",)
    # JAG001's allowed jit surfaces (fnmatch globs) — the rule itself, not
    # suppressions: these are the three places PR 2 left jit on purpose.
    jit_allowed: Tuple[str, ...] = (
        "src/repro/serve/executor.py",
        "src/repro/core/build.py",
        "src/repro/launch/*.py",
    )
    allow: Tuple[AllowEntry, ...] = ()


# ---------------------------------------------------------------------------
# config loading (pyproject.toml [tool.jagcheck])
# ---------------------------------------------------------------------------

def _parse_toml(text: str) -> dict:
    """Parse pyproject.toml — stdlib ``tomllib`` on 3.11+, else a minimal
    subset parser (tables, array-of-tables, strings, string arrays) that
    covers everything ``[tool.jagcheck]`` uses. Python 3.10 has no tomllib
    and the container must not grow dependencies."""
    try:
        import tomllib
        return tomllib.loads(text)
    except ModuleNotFoundError:
        pass
    root: dict = {}
    cur = root
    pending: Optional[str] = None  # key of a multiline array being read
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if pending is not None:
            cur[pending] += re.findall(r'"((?:[^"\\]|\\.)*)"', line)
            if line.rstrip(",").endswith("]"):
                pending = None
            continue
        m = re.fullmatch(r"\[\[([A-Za-z0-9_.\-]+)\]\]", line)
        if m:  # array-of-tables
            node = root
            parts = m.group(1).split(".")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            cur = {}
            node.setdefault(parts[-1], []).append(cur)
            continue
        m = re.fullmatch(r"\[([A-Za-z0-9_.\-]+)\]", line)
        if m:  # table
            node = root
            for p in m.group(1).split("."):
                node = node.setdefault(p, {})
            cur = node
            continue
        m = re.match(r'([A-Za-z0-9_\-]+)\s*=\s*(.+)$', line)
        if m:
            key, val = m.group(1), m.group(2).strip()
            if val.startswith("["):
                cur[key] = re.findall(r'"((?:[^"\\]|\\.)*)"', val)
                if not val.rstrip(",").endswith("]"):
                    pending = key  # array continues on following lines
            elif val.startswith('"'):
                mm = re.match(r'"((?:[^"\\]|\\.)*)"', val)
                cur[key] = mm.group(1) if mm else val.strip('"')
            elif val in ("true", "false"):
                cur[key] = val == "true"
            else:
                try:
                    cur[key] = int(val)
                except ValueError:
                    cur[key] = val
    return root


def load_config(root: str) -> Tuple[LintConfig, List[Finding]]:
    """Read ``[tool.jagcheck]`` from ``<root>/pyproject.toml``.

    Returns (config, config-errors): an allowlist entry missing its
    ``reason`` (or ``rule``/``path``) is a JAGCFG finding, not a crash —
    jagcheck must exit non-zero on it, same as on an unjustified finding.
    """
    path = os.path.join(root, "pyproject.toml")
    errors: List[Finding] = []
    if not os.path.exists(path):
        return LintConfig(), errors
    with open(path) as fh:
        data = _parse_toml(fh.read())
    cfg = data.get("tool", {}).get("jagcheck", {})
    allow: List[AllowEntry] = []
    for i, ent in enumerate(cfg.get("allow", [])):
        rule = str(ent.get("rule", "")).strip()
        glob = str(ent.get("path", "")).strip()
        reason = str(ent.get("reason", "")).strip()
        if not (rule in RULES and glob):
            errors.append(Finding(
                "JAGCFG", "pyproject.toml", 1,
                f"allow entry #{i + 1} needs a known rule and a path "
                f"(got rule={rule!r}, path={glob!r})"))
            continue
        if not reason:
            errors.append(Finding(
                "JAGCFG", "pyproject.toml", 1,
                f"allow entry #{i + 1} ({rule} {glob}) has no reason — "
                f"every suppression needs a one-line justification"))
            continue
        allow.append(AllowEntry(rule, glob, reason))
    out = LintConfig(
        include=tuple(cfg.get("include", LintConfig.include)),
        jit_allowed=tuple(cfg.get("jit_allowed", LintConfig.jit_allowed)),
        allow=tuple(allow))
    return out, errors


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> str:
    """'jax.jit' for Attribute(Name('jax'), 'jit'); '' if not a plain path."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jax_jit(node: ast.AST) -> bool:
    return _dotted(node) in ("jax.jit", "jit")


def _mentions_epoch(node: ast.AST) -> bool:
    """Does any name/attribute inside the expression contain 'epoch'?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and "epoch" in sub.attr.lower():
            return True
        if isinstance(sub, ast.Name) and "epoch" in sub.id.lower():
            return True
    return False


def _decorator_is_jit(dec: ast.AST) -> bool:
    """@jax.jit, @jit, @partial(jax.jit, ...), @functools.partial(jax.jit)."""
    if _is_jax_jit(dec):
        return True
    if isinstance(dec, ast.Call):
        if _is_jax_jit(dec.func):
            return True
        if _dotted(dec.func) in ("partial", "functools.partial") and \
                dec.args and _is_jax_jit(dec.args[0]):
            return True
    return False


def _decorator_is_lru(dec: ast.AST) -> bool:
    names = ("lru_cache", "functools.lru_cache", "cache", "functools.cache")
    if _dotted(dec) in names:
        return True
    return isinstance(dec, ast.Call) and _dotted(dec.func) in names


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------

def _jag001(tree: ast.AST, path: str, cfg: LintConfig) -> List[Finding]:
    if any(fnmatch.fnmatch(path, g) for g in cfg.jit_allowed):
        return []
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and _dotted(node) == "jax.jit":
            out.append(Finding(
                "JAG001", path, node.lineno,
                "jax.jit outside serve/executor.py, core/build.py and "
                "launch/ — serving compilations must go through the "
                "Executor's one epoch-keyed cache (PR 2 contract)"))
    return out


def _jag002(tree: ast.AST, path: str) -> List[Finding]:
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _dotted(node.func).split(".")[-1] == "einsum"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        spec = node.args[0].value.replace(" ", "")
        if spec == _EINSUM_SPEC:
            out.append(Finding(
                "JAG002", path, node.lineno,
                f'batch-variant einsum("{_EINSUM_SPEC}") candidate dot — '
                "use distances.gathered_dot: the batched-dot lowering "
                "varies its reduction with batch size, breaking per-query "
                "bit-identity (PR 3 contract)"))
    return out


def _jag003(tree: ast.Module, path: str) -> List[Finding]:
    out = []
    for node in tree.body:  # module level only: that is the bug class
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _decorator_is_lru(dec):
                    out.append(Finding(
                        "JAG003", path, dec.lineno if hasattr(dec, "lineno")
                        else node.lineno,
                        f"module-level lru_cache on {node.name}() can pin "
                        "device buffers process-wide (the PR 3 sample_ids "
                        "bug class) — cache on the owning object"))
        elif isinstance(node, ast.Assign) and isinstance(node.value,
                                                         ast.Call):
            call = node.value
            # x = lru_cache(...)(f)  /  x = lru_cache(f)
            if _decorator_is_lru(call.func) or _decorator_is_lru(call):
                out.append(Finding(
                    "JAG003", path, node.lineno,
                    "module-level lru_cache assignment can pin device "
                    "buffers process-wide (the PR 3 sample_ids bug class) "
                    "— cache on the owning object"))
    return out


def _jag004(tree: ast.AST, path: str) -> List[Finding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if not (isinstance(tgt, ast.Subscript)
                    and isinstance(tgt.value, ast.Attribute)
                    and tgt.value.attr == "_cache"):
                continue
            if not _mentions_epoch(tgt.slice):
                out.append(Finding(
                    "JAG004", path, node.lineno,
                    "_cache insertion key has no epoch component — an "
                    "epoch-less key serves stale compilations after a "
                    "streaming insert/compaction (PR 4 bug class)"))
    return out


class _JitRoots(ast.NodeVisitor):
    """Collect function nodes whose bodies jax.jit will trace.

    Three repo-idiomatic ways a function reaches the tracer:
      * decorated with ``@jax.jit`` / ``@partial(jax.jit, ...)``;
      * lexically wrapped — ``jax.jit(f)`` where ``f`` is a lambda or the
        name of a function defined in the same module scope;
      * defined inside an executor ``make()`` factory (the
        ``Executor.run(key, make, *args)`` convention jits whatever
        ``make()`` returns).
    """

    def __init__(self):
        self.roots: List[ast.AST] = []
        self._defs: Dict[str, ast.AST] = {}

    def visit_FunctionDef(self, node):
        self._defs[node.name] = node
        if any(_decorator_is_jit(d) for d in node.decorator_list):
            self.roots.append(node)
        if node.name == "make":
            for sub in ast.walk(node):
                if isinstance(sub, (ast.FunctionDef, ast.Lambda)) \
                        and sub is not node:
                    self.roots.append(sub)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        if _is_jax_jit(node.func) and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Lambda):
                self.roots.append(arg)
            elif isinstance(arg, ast.Name) and arg.id in self._defs:
                self.roots.append(self._defs[arg.id])
        self.generic_visit(node)


def _jag005(tree: ast.AST, path: str) -> List[Finding]:
    vis = _JitRoots()
    vis.visit(tree)
    out = []
    seen = set()
    for root in vis.roots:
        for node in ast.walk(root):
            if not isinstance(node, ast.Call) or node.lineno in seen:
                continue
            what = None
            fn = _dotted(node.func)
            if fn in ("np.asarray", "np.array", "numpy.asarray",
                      "numpy.array", "onp.asarray"):
                what = fn
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                what = ".item()"
            elif fn == "float" and node.args \
                    and not isinstance(node.args[0], ast.Constant):
                what = "float()"
            if what:
                seen.add(node.lineno)
                out.append(Finding(
                    "JAG005", path, node.lineno,
                    f"{what} inside a jit-traced function forces a "
                    "device->host sync (or silently constant-folds a "
                    "traced value)"))
    return out


_JAG006_TIMERS = ("time.time", "time.perf_counter", "time.monotonic",
                  "time.time_ns", "time.perf_counter_ns",
                  "time.monotonic_ns", "perf_counter", "monotonic")
_JAG006_MUTATORS = ("append", "observe", "inc", "record", "record_call")
_JAG006_SPANS = ("span", "TraceAnnotation")


def _jag006_chain(node: ast.AST) -> str:
    """Dotted chain like ``_dotted`` but seeing THROUGH calls.

    ``tel.metrics.counter("x").inc`` -> ``tel.metrics.counter.inc`` —
    registry accessors return the mutated object, so the owner test must
    not stop at the intervening ``Call`` node.
    """
    parts: List[str] = []
    while True:
        if isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Name):
            parts.append(node.id)
            break
        else:
            break
    return ".".join(reversed(parts))


def _jag006_owner_is_telemetry(chain: str) -> bool:
    """True when a dotted owner chain names a telemetry-ish object.

    Segments before the final attribute are checked: ``tel`` exactly, or
    anything containing ``telemetry``/``metric``/``trace`` — matching the
    ``repro.obs`` surface (Telemetry, TraceBuffer, MetricsRegistry) and
    the obvious local-variable spellings. ``trace_log`` is exempt: that
    is the executor's host-side audit hook, which lives in ``run()``
    (never traced) and predates the telemetry subsystem.
    """
    for seg in chain.lower().split(".")[:-1]:
        if seg == "trace_log":
            continue
        if seg == "tel" or "telemetry" in seg or "metric" in seg \
                or "trace" in seg:
            return True
    return False


def _jag006(tree: ast.AST, path: str) -> List[Finding]:
    vis = _JitRoots()
    vis.visit(tree)
    out = []
    seen = set()
    for root in vis.roots:
        for node in ast.walk(root):
            if not isinstance(node, ast.Call) or node.lineno in seen:
                continue
            fn = _dotted(node.func)
            chain = fn or _jag006_chain(node.func)
            what = None
            if chain.split(".")[-1] in _JAG006_SPANS:
                what = (f"span {chain}() inside a jit-traced function "
                        "times tracing, not execution; open the span "
                        "around the compiled call")
            elif fn in _JAG006_TIMERS:
                what = (f"{fn}() takes a host timestamp — under jit it "
                        "constant-folds at trace time; time in the "
                        "host-side wrapper around the route instead")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _JAG006_MUTATORS \
                    and _jag006_owner_is_telemetry(
                        fn or _jag006_chain(node.func)):
                what = (f"telemetry mutation "
                        f"{fn or _jag006_chain(node.func)}() — ring "
                        "buffers and "
                        "metric registries are host state; record after "
                        "the compiled route returns (repro.obs contract)")
            if what:
                seen.add(node.lineno)
                out.append(Finding("JAG006", path, node.lineno, what))
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def lint_source(src: str, path: str,
                cfg: Optional[LintConfig] = None) -> List[Finding]:
    """Lint one module's source text (``path`` is the repo-relative posix
    path the rules and allowlist match against). The unit the fixture
    tests drive via ``ast.parse`` on inline snippets."""
    cfg = cfg or LintConfig()
    tree = ast.parse(src)
    out = []
    out += _jag001(tree, path, cfg)
    out += _jag002(tree, path)
    out += _jag003(tree, path)
    out += _jag004(tree, path)
    out += _jag005(tree, path)
    out += _jag006(tree, path)
    return sorted(out, key=lambda f: (f.path, f.line, f.rule))


@dataclasses.dataclass
class LintReport:
    findings: List[Finding]          # unsuppressed — these fail the build
    suppressed: List[Tuple[Finding, AllowEntry]]
    config_errors: List[Finding]     # bad/stale allowlist entries

    @property
    def ok(self) -> bool:
        return not self.findings and not self.config_errors


def run_lint(root: str, cfg: Optional[LintConfig] = None,
             config_errors: Optional[Sequence[Finding]] = None) -> LintReport:
    """Lint every ``*.py`` under the config's include dirs.

    Findings matched by a justified allowlist entry are suppressed (and
    reported separately); allowlist entries that matched nothing become
    JAGCFG findings — a stale suppression would silently swallow the next
    real regression at that path.
    """
    if cfg is None:
        cfg, errs = load_config(root)
        config_errors = list(errs) + list(config_errors or [])
    findings: List[Finding] = []
    for inc in cfg.include:
        base = os.path.join(root, inc)
        for dirpath, _dirs, files in os.walk(base):
            for name in sorted(files):
                if not name.endswith(".py"):
                    continue
                full = os.path.join(dirpath, name)
                rel = os.path.relpath(full, root).replace(os.sep, "/")
                with open(full) as fh:
                    src = fh.read()
                try:
                    findings += lint_source(src, rel, cfg)
                except SyntaxError as e:
                    findings.append(Finding(
                        "JAGCFG", rel, e.lineno or 1,
                        f"unparseable module: {e.msg}"))
    kept: List[Finding] = []
    suppressed: List[Tuple[Finding, AllowEntry]] = []
    used = set()
    for f in findings:
        ent = next((a for a in cfg.allow
                    if a.rule == f.rule and fnmatch.fnmatch(f.path, a.path)),
                   None)
        if ent is not None:
            suppressed.append((f, ent))
            used.add((ent.rule, ent.path))
        else:
            kept.append(f)
    errs = list(config_errors or [])
    for a in cfg.allow:
        if (a.rule, a.path) not in used:
            errs.append(Finding(
                "JAGCFG", "pyproject.toml", 1,
                f"stale allowlist entry: {a.rule} {a.path} matched no "
                f"finding — remove it so it cannot mask a future one"))
    return LintReport(kept, suppressed, errs)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="jagcheck layer 1: repo-specific AST lint")
    ap.add_argument("--root", default=".", help="repo root")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="suppress the suppression summary")
    args = ap.parse_args(argv)
    report = run_lint(args.root)
    for f in report.findings + report.config_errors:
        print(f)
    if not args.quiet:
        for f, ent in report.suppressed:
            print(f"# allowed {f.rule} {f.path}:{f.line} — {ent.reason}")
    n = len(report.findings) + len(report.config_errors)
    print(f"# jagcheck lint: {n} finding(s), "
          f"{len(report.suppressed)} allowlisted")
    return 1 if n else 0


if __name__ == "__main__":
    raise SystemExit(main())
