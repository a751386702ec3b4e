"""jagcheck tests: per-rule lint fixtures + the compiled-route auditor.

Each JAG00x rule is demonstrated on a positive fixture reproducing its
original bug class (the PR 3 einsum, the PR 3 lru_cache, the PR 4
epoch-less cache key) AND a negative fixture of the sanctioned idiom, via
``ast.parse`` on inline snippets. The auditor section re-lowers every
executor route once (module-scoped report) and asserts the compiled
contracts; the sharded section runs on 8 faked devices in a subprocess,
mirroring tests/test_sharded.py.
"""
import textwrap

import pytest

from repro.analysis.lint import (AllowEntry, LintConfig, _parse_toml,
                                 lint_source, load_config, run_lint)

REPO = "/root/repo"


def codes(src, path="src/repro/serve/planner.py", cfg=None):
    return [f.rule for f in lint_source(textwrap.dedent(src), path, cfg)]


# ---------------------------------------------------------------------------
# Layer 1: one positive + one negative fixture per rule
# ---------------------------------------------------------------------------

def test_jag001_jit_outside_surface():
    src = """
    import jax
    step = jax.jit(lambda x: x + 1)
    """
    assert codes(src, "src/repro/core/jag.py") == ["JAG001"]
    # the three sanctioned jit surfaces pass untouched
    for ok in ("src/repro/serve/executor.py", "src/repro/core/build.py",
               "src/repro/launch/train.py"):
        assert codes(src, ok) == []


def test_jag001_decorator_form():
    src = """
    import jax
    from functools import partial

    @partial(jax.jit, static_argnums=0)
    def f(k, x):
        return x * k
    """
    assert codes(src, "src/repro/stream/index.py") == ["JAG001"]


def test_jag002_einsum_candidate_dot():
    # the PR 3 bug class verbatim (core/distributed.py:109 before this PR)
    src = """
    import jax.numpy as jnp

    def dist_fn(rows, q32, q_norm):
        d2 = (jnp.sum(rows * rows, -1)
              - 2.0 * jnp.einsum("bcd,bd->bc", rows, q32)
              + q_norm[:, None])
        return jnp.maximum(d2, 0.0)
    """
    assert codes(src) == ["JAG002"]
    # the sanctioned replacement, and a non-candidate-dot einsum spec
    ok = """
    import jax.numpy as jnp
    from repro.core.distances import gathered_dot

    def dist_fn(rows, q32):
        return gathered_dot(rows, q32) + jnp.einsum("bd,bd->b", rows[:, 0],
                                                    rows[:, 0])[:, None]
    """
    assert codes(ok) == []


def test_jag002_spec_whitespace_normalized():
    assert codes('import jax.numpy as jnp\n'
                 'y = jnp.einsum("bcd, bd -> bc", a, b)\n') == ["JAG002"]


def test_jag003_module_level_lru_cache():
    # the PR 3 sample_ids bug class: a module-level memo pinning buffers
    src = """
    import functools
    import jax.numpy as jnp

    @functools.lru_cache(maxsize=None)
    def sample_ids(n, n_samples, seed=0):
        return jnp.arange(n)[:n_samples]
    """
    assert codes(src) == ["JAG003"]
    assert codes("import functools\n"
                 "memo = functools.lru_cache(None)(lambda n: n)\n"
                 ) == ["JAG003"]
    # non-module-level (owned by an object) is the sanctioned shape
    ok = """
    import functools

    class Executor:
        @functools.lru_cache(maxsize=None)
        def _probe(self, n):
            return n
    """
    assert codes(ok) == []


def test_jag004_epoch_less_cache_key():
    # the PR 4 bug class: key omits the data epoch -> stale compilations
    src = """
    class Executor:
        def run(self, key, make, *args):
            fn = self._cache.get(key)
            if fn is None:
                fn = self._cache[key] = make()
            return fn(*args)
    """
    assert codes(src, "src/repro/serve/executor.py") == ["JAG004"]
    ok = """
    class Executor:
        def run(self, key, make, *args):
            fn = self._cache[(self._cache_epoch,) + key] = make()
            return fn(*args)
    """
    assert codes(ok, "src/repro/serve/executor.py") == []


def test_jag005_host_sync_in_jit_roots():
    # all three jit-root shapes: decorator, lexical wrap, make() factory.
    # Fixtures live on a JAG001-allowed path so only JAG005 is isolated
    # (a jax.jit on an unsanctioned path correctly fires JAG001 too).
    surface = "src/repro/core/build.py"
    dec = """
    import jax
    import numpy as np

    @jax.jit
    def f(x):
        return np.asarray(x).sum()
    """
    assert codes(dec, surface) == ["JAG005"]
    wrap = """
    import jax

    def g(x):
        return float(x)

    h = jax.jit(g)
    """
    assert codes(wrap, surface) == ["JAG005"]
    factory = """
    def make():
        def run(x):
            return x.item()
        return run
    """
    assert codes(factory) == ["JAG005"]
    # the same calls outside any jit root are host-side and fine
    ok = """
    import numpy as np

    def probe(x):
        return float(np.asarray(x).mean())
    """
    assert codes(ok) == []


def test_jag006_telemetry_in_jit_roots():
    # telemetry mutations inside an executor make() factory: the obs/
    # contract is host-side-after-return only
    surface = "src/repro/core/build.py"
    factory = """
    def make():
        def run(x):
            self.telemetry.traces.append(x)
            return x
        return run
    """
    assert codes(factory) == ["JAG006"]
    metric = """
    def make():
        def run(x):
            tel.metrics.counter("jag_x").inc()
            return x
        return run
    """
    assert codes(metric) == ["JAG006"]
    # host timestamps constant-fold at trace time inside a jit root
    timer = """
    import jax, time

    @jax.jit
    def f(x):
        t0 = time.perf_counter()
        return x + t0
    """
    assert codes(timer, surface) == ["JAG006"]


def test_jag006_spans_in_jit_roots():
    # a span inside a traced function times tracing, not execution
    factory = """
    def make():
        def run(x):
            with span("plan"):
                return x
        return run
    """
    assert codes(factory) == ["JAG006"]
    annotated = """
    import jax

    @jax.jit
    def f(x):
        with jax.profiler.TraceAnnotation("jag.x"):
            return x + 1
    """
    assert codes(annotated, "src/repro/core/build.py") == ["JAG006"]
    # around the compiled call, on the host, a span is the contract
    host = """
    def launch(fn, x):
        with span("execute:graph", rec, queries=4):
            return fn(x)
    """
    assert codes(host) == []


def test_jag006_host_side_telemetry_is_fine():
    # the actual dispatch/search_auto wrapper shape: timing + recording
    # around (not inside) the compiled route
    ok = """
    import time

    def timed(route, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(route(*args))
        tel.metrics.counter("jag_route_call_total").inc()
        tel.traces.append(out)
        return out, time.perf_counter() - t0
    """
    assert codes(ok) == []
    # a plain list append inside a make() factory is not telemetry
    plain = """
    def make():
        def run(xs):
            out = []
            out.append(xs)
            return out
        return run
    """
    assert codes(plain) == []
    # the executor's trace_log analysis hook is exempt by name
    log = """
    def make():
        def run(x):
            self.trace_log.append(x)
            return x
        return run
    """
    assert codes(log) == []


def test_lint_real_executor_passes():
    with open(f"{REPO}/src/repro/serve/executor.py") as fh:
        assert codes(fh.read(), "src/repro/serve/executor.py") == []


# ---------------------------------------------------------------------------
# config / allowlist
# ---------------------------------------------------------------------------

def test_toml_fallback_parser_multiline_arrays():
    data = _parse_toml(textwrap.dedent("""
        [tool.jagcheck]
        include = ["src/repro"]
        jit_allowed = [
            "a.py",
            "b/*.py",
        ]

        [[tool.jagcheck.allow]]
        rule = "JAG001"
        path = "c.py"
        reason = "because"
    """))
    cfg = data["tool"]["jagcheck"]
    assert cfg["include"] == ["src/repro"]
    assert cfg["jit_allowed"] == ["a.py", "b/*.py"]
    assert cfg["allow"] == [{"rule": "JAG001", "path": "c.py",
                             "reason": "because"}]


def test_allow_entry_requires_reason(tmp_path):
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [[tool.jagcheck.allow]]
        rule = "JAG001"
        path = "src/repro/x.py"
    """))
    cfg, errors = load_config(str(tmp_path))
    assert not cfg.allow
    assert [e.rule for e in errors] == ["JAGCFG"]
    assert "reason" in errors[0].msg


def test_stale_allowlist_entry_is_flagged(tmp_path):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "clean.py").write_text("x = 1\n")
    cfg = LintConfig(allow=(AllowEntry("JAG002", "src/repro/gone.py",
                                       "used to matter"),))
    report = run_lint(str(tmp_path), cfg, [])
    assert not report.findings
    assert [e.rule for e in report.config_errors] == ["JAGCFG"]
    assert "stale" in report.config_errors[0].msg


def test_repo_lint_is_burned_down():
    """The satellite contract: zero unjustified findings on the repo."""
    report = run_lint(REPO)
    assert report.ok, [str(f) for f in
                       report.findings + report.config_errors]
    # the live JAG002 violation this PR fixed must NOT be suppressed
    assert not any(f.path == "src/repro/core/distributed.py"
                   for f, _ in report.suppressed)


# ---------------------------------------------------------------------------
# Layer 2: HLO text parsers on synthetic fixtures
# ---------------------------------------------------------------------------

def test_while_region_and_call_resolution():
    from repro.analysis.audit import _expansion_gathers
    stable = textwrap.dedent("""\
    module @jit_f {
      func.func public @main(%arg0: tensor<256x8xf32> {x.y = "z"}) -> tensor<4xf32> {
        %0 = "stablehlo.gather"(%arg0, %c) <{g = #stablehlo.gather<a = [2]>, s = array<i64: 1, 8>}> : (tensor<256x8xf32>, tensor<4x1xi32>) -> tensor<4x8xf32>
        %1:2 = stablehlo.while(%iterArg = %arg0, %iterArg_1 = %0) : tensor<256x8xf32>, tensor<4x8xf32>
         cond {
          stablehlo.return %t : tensor<i1>
         } do {
          %2 = "stablehlo.gather"(%adj, %i) <{s = array<i64: 1, 22>}> : (tensor<256x22xi32>, tensor<4x16x1xi32>) -> tensor<4x16x22xi32>
          %3 = call @_take(%iterArg, %2) : (tensor<256x8xf32>, tensor<4x16x22xi32>) -> tensor<4x16x8xf32>
          stablehlo.return %iterArg, %3#0 : tensor<256x8xf32>, tensor<4x8xf32>
         }
        return %1#1 : tensor<4xf32>
      }
      func.func private @_take(%arg0: tensor<256x8xf32>, %arg1: tensor<4x16x22xi32>) -> tensor<4x16x8xf32> {
        %0 = "stablehlo.gather"(%arg0, %arg1) <{s = array<i64: 1, 8>}> : (tensor<256x8xf32>, tensor<4x16x1xi32>) -> tensor<4x16x8xf32>
        return %0 : tensor<4x16x8xf32>
      }
    }
    """)
    # entry gather is OUTSIDE the loop; in-loop = adjacency + 1 outlined
    # data gather reached through call @_take -> exactly 1 per expansion
    assert _expansion_gathers(stable, 256, "256x22xi32") == 1
    assert _expansion_gathers(stable, 256, "999x9xi32") is None


def test_gather_operand_parser_ignores_references():
    from repro.analysis.audit import _gather_operands
    line = ('%6 = "stablehlo.gather"(%a, %b) <{s = array<i64: 1, 10>}> : '
            '(tensor<320x10xf32>, tensor<6x16x1xi32>) -> tensor<6x16x10xf32>')
    assert _gather_operands(line) == ["320x10xf32"]
    assert _gather_operands('stablehlo.return "stablehlo.gather"') == []


# ---------------------------------------------------------------------------
# Layer 2: the real executor routes (one build+lower pass, module-scoped)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def audit_report():
    from repro.analysis.audit import audit_single_device
    return audit_single_device()


def test_audit_covers_every_route(audit_report):
    graph = {f"graph:{la}:{dt}" for la in ("default", "fused")
             for dt in ("f32", "int8")}
    assert set(audit_report["routes"]) == (
        {"prefilter", "postfilter", "unfiltered", "delta", "merge"}
        | graph | {g + ":introspect" for g in graph})
    # PR 9: the audited programs were captured WITH telemetry attached —
    # the zero-callback budgets below therefore prove tracing adds none
    assert audit_report["meta"]["telemetry"] is True


def test_audit_introspective_routes_match_their_twins(audit_report):
    # PR 10: the introspective compilation may add counters but must not
    # add gathers, callbacks, or collectives relative to its twin route
    routes = audit_report["routes"]
    twins = [n for n in routes if n.endswith(":introspect")]
    assert len(twins) == 4
    for name in twins:
        twin = routes[name.rsplit(":introspect", 1)[0]]
        r = routes[name]
        assert r["gathers_per_expansion"] == twin["gathers_per_expansion"]
        assert r["callbacks"] == 0 and r["collectives"] == {}


def test_audit_fused_routes_one_gather_per_expansion(audit_report):
    for name, r in audit_report["routes"].items():
        if name.startswith("graph:fused"):
            assert r["gathers_per_expansion"] == 1, (name, r)
            assert r["adjacency_gathers"] >= 1, (name, r)
        elif name.startswith("graph:default") or name in ("postfilter",
                                                          "unfiltered"):
            # split layout: vector + norm + attr fetches per expansion
            assert r["gathers_per_expansion"] == 3, (name, r)
        else:  # scans and merges have no traversal loop
            assert r["gathers_per_expansion"] is None, (name, r)


def test_audit_no_callbacks_f64_or_collectives(audit_report):
    for name, r in audit_report["routes"].items():
        assert r["callbacks"] == 0, (name, r)
        assert r["f64_ops"] == 0, (name, r)
        assert r["collectives"] == {}, (name, r)


def test_audit_check_report_flags_violations(audit_report):
    from repro.analysis.audit import check_report
    assert check_report(audit_report) == []
    import copy
    bad = copy.deepcopy(audit_report)
    bad["routes"]["graph:fused:f32"]["gathers_per_expansion"] = 2
    bad["routes"]["prefilter"]["callbacks"] = 1
    bad["sharded"] = {"routes": {"graph": {
        "callbacks": 0, "f64_ops": 0,
        "collectives": {"all-gather": 2}}}}
    msgs = check_report(bad)
    assert any("graph:fused:f32" in m for m in msgs)
    assert any("prefilter" in m and "callback" in m for m in msgs)
    assert any("sharded/graph" in m for m in msgs)


def test_audit_sharded_subprocess():
    """Sharded routes on 8 faked devices: exactly one all-gather each."""
    from repro.analysis.audit import check_report, run_sharded_audit
    sh = run_sharded_audit(REPO)
    assert set(sh["routes"]) == {"prefilter", "graph", "postfilter",
                                 "unfiltered"}
    for name, r in sh["routes"].items():
        assert r["collectives"] == {"all-gather": 1}, (name, r)
        assert r["callbacks"] == 0 and r["f64_ops"] == 0, (name, r)
        # the one collective moves the packed [B, 3k+2] int32 payload
        assert r["collective_bytes"]["all-gather"] == (
            sh["meta"]["devices"] * sh["meta"]["merge_payload_bytes"])
    assert check_report({"routes": {}, "sharded": sh}) == []
