"""dqlint framework + rule suite (ISSUE 8).

Every rule is proven LIVE by a synthetic offender tree (a finding the
rule must produce), proven QUIET by the sanctioned spelling of the same
code, and proven SUPPRESSIBLE by pragma and baseline. The final class
pins the real tree clean through the ``scripts/check_static.py`` CLI —
the tier-1 gate itself.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

from sparkdq4ml_tpu.analysis import (Baseline, get_rules,  # noqa: E402
                                     run_rules)
from sparkdq4ml_tpu.analysis.core import SourceFile  # noqa: E402

pytestmark = pytest.mark.static_analysis


def tree(tmp_path, files: dict):
    """Write a synthetic sparkdq4ml_tpu package tree; returns its root."""
    for rel, content in files.items():
        p = tmp_path / "sparkdq4ml_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(content))
    return str(tmp_path)


def findings_for(tmp_path, files, rules):
    f, _ = run_rules(tree(tmp_path, files), get_rules(rules))
    return f


# ---------------------------------------------------------------------------
# Core framework: pragmas, baseline, single parse
# ---------------------------------------------------------------------------

class TestFrameworkCore:
    def test_line_pragma_parsing_single_and_multi(self, tmp_path):
        src = SourceFile(__file__, "x.py", text=(
            "a = 1  # dqlint: ok(host-sync)\n"
            "b = 2  # dqlint: ok(noop, lock-order): reasoned\n"
            "c = 3\n"))
        assert src.line_pragmas[1] == {"host-sync"}
        assert src.line_pragmas[2] == {"noop", "lock-order"}
        assert 3 not in src.line_pragmas

    def test_comment_pragma_covers_following_statement(self, tmp_path):
        text = ("def f():\n"
                "    # dqlint: ok(host-sync): spans the whole call\n"
                "    return g(\n"
                "        h(),\n"
                "    )\n")
        src = SourceFile(__file__, "x.py", text=text)
        import ast
        call = [n for n in ast.walk(src.tree)
                if isinstance(n, ast.Call)][-1]   # h() on line 4
        assert src.pragma_covers("host-sync", call)
        assert not src.pragma_covers("noop", call)

    def test_comment_pragma_does_not_blanket_the_function(self):
        text = ("def f():\n"
                "    # dqlint: ok(host-sync)\n"
                "    a = 1\n"
                "    b = 2\n")
        src = SourceFile(__file__, "x.py", text=text)
        import ast
        stmts = src.tree.body[0].body
        assert src.pragma_covers("host-sync", stmts[0])
        assert not src.pragma_covers("host-sync", stmts[1])

    def test_file_pragma(self):
        src = SourceFile(__file__, "x.py", text=(
            "# dqlint: ok-file(host-sync): host-side module\n"
            "x = 1\n"))
        import ast
        assert src.pragma_covers("host-sync", src.tree.body[0])
        assert not src.pragma_covers("noop", src.tree.body[0])

    def test_baseline_roundtrip_and_stale(self, tmp_path):
        root = tree(tmp_path, {"frame/mod.py": """
            import jax

            def leak(x):
                return jax.device_get(x)
            """})
        bl_path = str(tmp_path / "baseline.json")
        f, _ = run_rules(root, get_rules(["host-sync"]))
        assert len(f) == 1
        bl = Baseline(bl_path)
        bl.write(f)
        # same findings now arrive baselined
        f2, stale = run_rules(root, get_rules(["host-sync"]),
                              Baseline(bl_path))
        assert all(x.baselined for x in f2) and not stale
        # fix the code -> the entry goes stale
        (tmp_path / "sparkdq4ml_tpu" / "frame" / "mod.py").write_text(
            "def leak(x):\n    return x\n")
        f3, stale3 = run_rules(root, get_rules(["host-sync"]),
                               Baseline(bl_path))
        assert f3 == [] and len(stale3) == 1

    def test_unknown_rule_name_rejected(self):
        with pytest.raises(ValueError, match="unknown rule"):
            get_rules(["no-such-rule"])


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

OFFENDER_HOST_SYNC = {"frame/leaky.py": """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def pull(arr):
        return jax.device_get(arr)

    def scalar(arr):
        return float(jnp.sum(arr))

    def listy(col):
        return col.tolist()

    def convert(x):
        return np.asarray(jnp.abs(x))
    """}


class TestHostSyncRule:
    def test_offenders_flagged(self, tmp_path):
        f = findings_for(tmp_path, OFFENDER_HOST_SYNC, ["host-sync"])
        lines = {x.line for x in f}
        assert len(f) == 4 and all(x.rule == "host-sync" for x in f)
        assert {7, 10, 13, 16} == lines

    def test_counted_wrapper_sanctions(self, tmp_path):
        f = findings_for(tmp_path, {"frame/ok.py": """
            import jax
            from ..utils.profiling import counters

            def pull(arr):
                counters.increment("frame.host_sync")
                return jax.device_get(arr)

            def via_helper(frame):
                d = frame.to_pydict()
                return d["a"].tolist()
            """}, ["host-sync"])
        assert f == []

    def test_the_read_wrapper_sanctions_and_a_pull_beside_it_does_not(
            self, tmp_path):
        f = findings_for(tmp_path, {"models/reads.py": """
            import jax.numpy as jnp
            import numpy as np
            from ..utils import observability as _obs
            from ..utils.observability import host_reading

            def wrapped(x):
                with host_reading("fit.result") as rd:
                    out = np.asarray(jnp.abs(x))
                    rd.done(out.nbytes)
                return out

            def wrapped_by_module(x):
                with _obs.host_reading("fit.result") as rd:
                    n = int(jnp.sum(x))
                    rd.done(4)
                return n

            def bare(x):
                return np.asarray(jnp.abs(x))
            """}, ["host-sync"])
        assert len(f) == 1 and f[0].line == 20

    def test_numpy_receivers_and_annotations_are_quiet(self, tmp_path):
        f = findings_for(tmp_path, {"frame/hosty.py": """
            import numpy as np

            def a(values: np.ndarray):
                return values.tolist()

            def b(x):
                arr = np.asarray(x, object).ravel()
                v = arr[0]
                return v.item()
            """}, ["host-sync"])
        assert f == []

    def test_pragma_suppresses(self, tmp_path):
        f = findings_for(tmp_path, {"frame/pragma.py": """
            import jax

            def pull(arr):
                # dqlint: ok(host-sync): test exemption
                return jax.device_get(arr)
            """}, ["host-sync"])
        assert f == []

    def test_module_level_transfer_flagged(self, tmp_path):
        # import-time transfers have no wrapper by definition
        f = findings_for(tmp_path, {"models/table.py": """
            import jax.numpy as jnp
            import numpy as np

            _TABLE = np.asarray(jnp.exp(jnp.arange(100.0)))
            """}, ["host-sync"])
        assert len(f) == 1 and f[0].line == 5

    def test_gc_collect_does_not_sanction(self, tmp_path):
        # regression: a call on an imported MODULE whose rightmost name
        # collides with a counted wrapper (gc.collect) must not mark the
        # function counted
        f = findings_for(tmp_path, {"serve/pool.py": """
            import gc

            import jax.numpy as jnp

            def trim(arr):
                gc.collect()
                return float(jnp.sum(arr))
            """}, ["host-sync"])
        assert len(f) == 1 and f[0].line == 8

    def test_out_of_scope_dirs_quiet(self, tmp_path):
        f = findings_for(tmp_path, {"utils/tooling.py": """
            import jax

            def pull(arr):
                return jax.device_get(arr)
            """}, ["host-sync"])
        assert f == []


# ---------------------------------------------------------------------------
# collective-guard
# ---------------------------------------------------------------------------

class TestCollectiveGuardRule:
    def test_unguarded_factory_flagged(self, tmp_path):
        f = findings_for(tmp_path, {"models/badfit.py": """
            import jax
            from ..parallel.mesh import shard_map

            def make_fit(mesh):
                fn = shard_map(lambda x: x, mesh=mesh, in_specs=(),
                               out_specs=())
                return jax.jit(fn)
            """}, ["collective-guard"])
        assert len(f) == 1 and f[0].rule == "collective-guard"

    def test_guarded_factory_clean(self, tmp_path):
        f = findings_for(tmp_path, {"models/goodfit.py": """
            import jax
            from ..parallel.mesh import serialize_collectives, shard_map

            def make_fit(mesh):
                fn = shard_map(lambda x: x, mesh=mesh, in_specs=(),
                               out_specs=())
                return serialize_collectives(jax.jit(fn), mesh)
            """}, ["collective-guard"])
        assert f == []

    def test_psum_helper_without_dispatch_is_not_a_factory(self, tmp_path):
        f = findings_for(tmp_path, {"models/core.py": """
            import jax

            def local_objective(w, X):
                return jax.lax.psum(X @ w, "data")
            """}, ["collective-guard"])
        assert f == []

    def test_jitted_psum_program_flagged(self, tmp_path):
        f = findings_for(tmp_path, {"models/badcore.py": """
            import jax

            def make(mesh):
                def obj(w, X):
                    return jax.lax.psum(X @ w, "data")
                return jax.jit(obj)
            """}, ["collective-guard"])
        assert len(f) == 1

    def test_pragma_suppresses(self, tmp_path):
        f = findings_for(tmp_path, {"models/exempt.py": """
            import jax
            from ..parallel.mesh import shard_map

            def make(mesh):
                # dqlint: ok(collective-guard): caller wraps the dispatch
                fn = shard_map(lambda x: x, mesh=mesh, in_specs=(),
                               out_specs=())
                return jax.jit(fn)
            """}, ["collective-guard"])
        assert f == []


# ---------------------------------------------------------------------------
# conf-key
# ---------------------------------------------------------------------------

CONF_CONFIG = {"config.py": """
    CONF_FALSE = ("false", "off", "0", "no")
    CONF_TRUE = ("true", "on", "1", "yes")
    CONF_KEYS = {
        "spark.pipeline.enabled": "session",
        "spark.compilation.cache": "init",
    }
    CONF_KEY_PREFIXES = ("spark.serve.",)
    """,
    "session.py": """
    class S:
        def _init_pipeline(self):
            v = self.conf.get("spark.pipeline.enabled", "")
    """}


class TestConfKeyRule:
    def test_undeclared_key_flagged(self, tmp_path):
        files = dict(CONF_CONFIG)
        files["frame/reader.py"] = """
            def f(conf):
                return conf.get("spark.bogus.key", "")
            """
        f = findings_for(tmp_path, files, ["conf-key"])
        assert len(f) == 1 and "spark.bogus.key" in f[0].message

    def test_declared_exact_prefix_and_fstring_clean(self, tmp_path):
        files = dict(CONF_CONFIG)
        files["frame/reader.py"] = """
            def f(conf, key):
                a = conf.get("spark.pipeline.enabled")
                b = conf.get(f"spark.serve.{key}")
                c = [k for k in conf if k.startswith("spark.pipeline.")]
                return a, b, c
            """
        f = findings_for(tmp_path, files, ["conf-key"])
        assert f == []

    def test_session_key_must_be_in_init_pipeline(self, tmp_path):
        files = dict(CONF_CONFIG)
        files["config.py"] = files["config.py"].replace(
            '"spark.compilation.cache": "init",',
            '"spark.compilation.cache": "init",\n'
            '        "spark.orphan.enabled": "session",')
        f = findings_for(tmp_path, files, ["conf-key"])
        assert len(f) == 1 and "spark.orphan.enabled" in f[0].message \
            and "_init_pipeline" in f[0].message

    def test_truncated_key_is_not_a_namespace_probe(self, tmp_path):
        # regression: "spark.pipeline.enable" (dropped final 'd') is a
        # string prefix of the declared key but NOT a probe — only
        # dot-terminated literals get prefix matching
        files = dict(CONF_CONFIG)
        files["frame/reader.py"] = """
            def f(conf):
                return conf.get("spark.pipeline.enable", "")
            """
        f = findings_for(tmp_path, files, ["conf-key"])
        assert len(f) == 1 and "spark.pipeline.enable" in f[0].message

    def test_inline_truthiness_tuple_flagged(self, tmp_path):
        files = dict(CONF_CONFIG)
        files["frame/reader.py"] = """
            def f(conf):
                return str(conf.get("spark.compilation.cache")) in ("true", "1")
            """
        f = findings_for(tmp_path, files, ["conf-key"])
        assert len(f) == 1 and "CONF_TRUE" in f[0].message

    def test_shared_vocabulary_spelling_clean(self, tmp_path):
        files = dict(CONF_CONFIG)
        files["frame/reader.py"] = """
            from ..config import CONF_TRUE

            def f(conf):
                return str(conf.get("spark.compilation.cache")) in CONF_TRUE
            """
        f = findings_for(tmp_path, files, ["conf-key"])
        assert f == []

    def test_non_conf_keyword_tuples_unflagged(self, tmp_path):
        files = dict(CONF_CONFIG)
        files["sql/kw.py"] = """
            def is_join_kw(tok):
                return tok.lower() in ("left", "right")
            """
        f = findings_for(tmp_path, files, ["conf-key"])
        assert f == []


# ---------------------------------------------------------------------------
# noop
# ---------------------------------------------------------------------------

class TestNoopContractRule:
    def test_fstring_span_arg_flagged(self, tmp_path):
        f = findings_for(tmp_path, {"frame/tracey.py": """
            from ..utils import observability as _obs

            def run(name):
                with _obs.span("op", cat="frame", tag=f"plan[{name}]"):
                    pass
            """}, ["noop"])
        assert len(f) == 1 and f[0].rule == "noop"

    def test_current_span_set_format_flagged_and_guard_sanctions(
            self, tmp_path):
        f = findings_for(tmp_path, {"frame/t2.py": """
            from ..utils import observability as _obs

            def bad(name):
                _obs.current_span().set(plan="View[%s]" % name)

            def good(name):
                if _obs.TRACER.enabled:
                    _obs.current_span().set(plan=f"View[{name}]")

            def early(name):
                if not _obs.TRACER.enabled:
                    return None
                _obs.current_span().set(plan=f"View[{name}]")
            """}, ["noop"])
        assert len(f) == 1 and f[0].line == 5

    def test_span_var_set_tracked_through_with(self, tmp_path):
        f = findings_for(tmp_path, {"frame/t3.py": """
            from ..utils import observability as _obs

            def run(q):
                with _obs.span("sql.query", cat="sql") as s:
                    s.set(query=" ".join(q.split()))
            """}, ["noop"])
        assert len(f) == 1

    def test_raw_value_attrs_clean(self, tmp_path):
        f = findings_for(tmp_path, {"frame/t4.py": """
            from ..utils import observability as _obs

            def run(rows, bucket):
                with _obs.span("flush", cat="frame", rows=rows,
                               bucket=bucket) as s:
                    s.set(groups=rows - 1)
            """}, ["noop"])
        assert f == []

    def test_direct_span_allocation_flagged(self, tmp_path):
        f = findings_for(tmp_path, {"frame/t5.py": """
            def run():
                return Span("rogue")
            """}, ["noop"])
        assert len(f) == 1 and "Span" in f[0].message


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------

class TestLockOrderRule:
    def test_inversion_flagged(self, tmp_path):
        f = findings_for(tmp_path, {"serve/locked.py": """
            import threading

            _A = threading.Lock()
            _B = threading.Lock()

            def one():
                with _A:
                    with _B:
                        pass

            def other():
                with _B:
                    with _A:
                        pass
            """}, ["lock-order"])
        assert len(f) == 1 and "inversion" in f[0].message

    def test_consistent_order_clean(self, tmp_path):
        f = findings_for(tmp_path, {"serve/locked.py": """
            import threading

            _A = threading.Lock()
            _B = threading.Lock()

            def one():
                with _A:
                    with _B:
                        pass

            def other():
                with _A:
                    with _B:
                        pass
            """}, ["lock-order"])
        assert f == []

    def test_call_propagated_inversion(self, tmp_path):
        f = findings_for(tmp_path, {"serve/prop.py": """
            import threading

            _A = threading.Lock()
            _B = threading.Lock()

            def takes_b():
                with _B:
                    pass

            def takes_a_then_calls():
                with _A:
                    takes_b()

            def other():
                with _B:
                    with _A:
                        pass
            """}, ["lock-order"])
        assert len(f) == 1 and "inversion" in f[0].message

    def test_instance_locks_and_self_method_propagation(self, tmp_path):
        f = findings_for(tmp_path, {"serve/inst.py": """
            import threading

            class Srv:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._series = threading.Lock()

                def a_then_b(self):
                    with self._cond:
                        with self._series:
                            pass

                def b_then_a(self):
                    with self._series:
                        with self._cond:
                            pass
            """}, ["lock-order"])
        assert len(f) == 1 and "inversion" in f[0].message

    def test_bare_acquire_flagged_with_guarded(self, tmp_path):
        f = findings_for(tmp_path, {"serve/bare.py": """
            import threading

            _A = threading.Lock()

            def bad():
                _A.acquire()
                work()
                _A.release()

            def good():
                _A.acquire()
                try:
                    work()
                finally:
                    _A.release()
            """}, ["lock-order"])
        assert len(f) == 1 and "acquire" in f[0].message and f[0].line == 7

    def test_acquire_style_inversion_caught(self, tmp_path):
        # regression: a lock taken via bare .acquire() must extend the
        # held set so the opposite `with` ordering is an inversion
        f = findings_for(tmp_path, {"serve/cond.py": """
            import threading

            _A = threading.Lock()
            _B = threading.Lock()

            def acq_style():
                _A.acquire()
                try:
                    with _B:
                        pass
                finally:
                    _A.release()

            def with_style():
                with _B:
                    with _A:
                        pass
            """}, ["lock-order"])
        assert len(f) == 1 and "inversion" in f[0].message

    def test_dict_clear_does_not_alias_lock_methods(self, tmp_path):
        # regression: dict.clear() under lock A must not resolve to
        # another class's clear() that takes lock B
        f = findings_for(tmp_path, {"utils/reg.py": """
            import threading

            class A:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._d = {}

                def clear(self):
                    with self._lock:
                        self._d.clear()

            class B:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._d = {}

                def clear(self):
                    with self._lock:
                        self._d.clear()
            """}, ["lock-order"])
        assert f == []


# ---------------------------------------------------------------------------
# the framework ports of the legacy lints stay live through the new CLI
# ---------------------------------------------------------------------------

class TestLegacyPortedRules:
    def test_logger_ns_through_framework(self, tmp_path):
        f = findings_for(tmp_path, {"rogue.py": """
            import logging

            log = logging.getLogger("rogue.ns")
            """}, ["logger-ns"])
        assert len(f) == 1

    def test_numpy_free_through_framework(self, tmp_path):
        f = findings_for(tmp_path, {"ops/segments.py": """
            import numpy as np

            x = np.asarray([1.0])
            # --- BEGIN HOST FALLBACK
            y = np.asarray([2.0])
            # --- END HOST FALLBACK
            """}, ["numpy-free"])
        assert {x.line for x in f} == {2, 4}


# ---------------------------------------------------------------------------
# fault-site: chaos hook call sites name registered sites/kinds
# ---------------------------------------------------------------------------

_FAULTS_STUB = """
    KINDS = ("device_error", "nan", "torn_chunk")

    FAULT_SITES = {
        "pipeline_flush": ("device_error", "nan"),
        "ingest_native": ("torn_chunk",),
    }

    def inject(site):
        pass

    def corrupt(site, tree):
        return tree

    def fired(site, kind):
        return False
    """


class TestFaultSiteRule:
    def _tree(self, tmp_path, body):
        return findings_for(tmp_path, {
            "utils/faults.py": _FAULTS_STUB,
            "frame/mod.py": body}, ["fault-site"])

    def test_registered_literal_sites_are_quiet(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils import faults as _faults

            def flush():
                _faults.inject("pipeline_flush")
                if _faults.fired("ingest_native", "torn_chunk"):
                    return None
                return _faults.corrupt("pipeline_flush", {})
            """)
        assert f == []

    def test_typod_site_flagged(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils import faults as _faults

            def flush():
                _faults.inject("pipleine_flush")
            """)
        assert len(f) == 1 and "not registered" in f[0].message

    def test_computed_site_flagged(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils import faults as _faults

            def flush(site):
                _faults.inject(site)
            """)
        assert len(f) == 1 and "LITERAL" in f[0].message

    def test_unregistered_kind_flagged(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils import faults as _faults

            def flush():
                _faults.fired("ingest_native", "thread_death")
            """)
        assert len(f) == 1 and "thread_death" in f[0].message

    def test_keyword_form_is_checked_too(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils import faults as _faults

            def flush():
                _faults.inject(site="pipeline_flush")      # ok
                _faults.fired("ingest_native", kind="thread_deth")
            """)
        assert len(f) == 1 and "thread_deth" in f[0].message

    def test_bare_import_form_is_matched(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils.faults import inject

            def flush():
                inject("nope_site")
            """)
        assert len(f) == 1 and "nope_site" in f[0].message

    def test_pragma_suppresses(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils import faults as _faults

            def flush():
                _faults.inject("dynamic_site")  # dqlint: ok(fault-site): test-only site
            """)
        assert f == []

    def test_missing_registry_is_a_finding(self, tmp_path):
        f = findings_for(tmp_path, {
            "utils/faults.py": "KINDS = ()\n",
            "frame/mod.py": """
                from ..utils import faults as _faults

                def flush():
                    _faults.inject("pipeline_flush")
                """}, ["fault-site"])
        assert len(f) == 1 and "FAULT_SITES" in f[0].message

    def test_partial_tree_without_faults_module_is_quiet(self, tmp_path):
        f = findings_for(tmp_path, {"frame/mod.py": """
            from ..utils import faults as _faults

            def flush():
                _faults.inject("whatever")
            """}, ["fault-site"])
        assert f == []


# ---------------------------------------------------------------------------
# metric-name: increment/set_gauge/observe literals resolve to the registry
# ---------------------------------------------------------------------------

_OBS_STUB = """
    METRIC_NAMES = {
        "pipeline.hit": ("counter", "replays"),
        "serve.queue_depth": ("gauge", "queued jobs"),
        "serve.e2e_ms": ("histogram", "latency"),
    }

    METRIC_NAME_PREFIXES = {
        "recovery.": ("counter", "resilience events"),
        "serve.e2e_ms.": ("histogram", "per-tenant latency"),
    }
"""


class TestMetricNameRule:
    def _tree(self, tmp_path, body):
        return findings_for(tmp_path, {
            "utils/observability.py": _OBS_STUB,
            "frame/mod.py": body}, ["metric-name"])

    def test_registered_names_are_quiet(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils.profiling import counters
            from ..utils import observability as _obs

            def flush(tenant):
                counters.increment("pipeline.hit")
                counters.increment(f"recovery.{'retry'}")
                _obs.METRICS.set_gauge("serve.queue_depth", 1)
                _obs.METRICS.observe("serve.e2e_ms", 2.0)
                _obs.METRICS.observe(f"serve.e2e_ms.{tenant}", 2.0)
            """)
        assert f == []

    def test_typod_counter_flagged(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils.profiling import counters

            def flush():
                counters.increment("pipleine.hit")
            """)
        assert len(f) == 1 and "pipleine.hit" in f[0].message

    def test_unregistered_gauge_flagged(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils import observability as _obs

            def flush():
                _obs.METRICS.set_gauge("serve.depth_queue", 1)
            """)
        assert len(f) == 1 and "serve.depth_queue" in f[0].message

    def test_undeclared_fstring_family_flagged(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils.profiling import counters

            def flush(site):
                counters.increment(f"mystery.{site}")
            """)
        assert len(f) == 1 and "METRIC_NAME_PREFIXES" in f[0].message

    def test_computed_name_flagged_conditional_literals_ok(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils.profiling import counters

            def flush(name, missed):
                counters.increment(name)
                counters.increment(
                    "pipeline.hit" if missed else "serve.queue_depth")
            """)
        assert len(f) == 1 and "LITERAL" in f[0].message

    def test_unqualified_receiver_ignored(self, tmp_path):
        f = self._tree(tmp_path, """
            def flush(store):
                store.increment("not.a.metric")
                store.observe("whatever", 1.0)
            """)
        assert f == []

    def test_pragma_suppresses(self, tmp_path):
        f = self._tree(tmp_path, """
            from ..utils.profiling import counters

            def flush():
                counters.increment("adhoc.series")  # dqlint: ok(metric-name): test-only
            """)
        assert f == []

    def test_missing_registry_is_a_finding(self, tmp_path):
        f = findings_for(tmp_path, {
            "utils/observability.py": "X = 1\n",
            "frame/mod.py": """
                from ..utils.profiling import counters

                def flush():
                    counters.increment("pipeline.hit")
                """}, ["metric-name"])
        assert len(f) == 1 and "METRIC_NAMES" in f[0].message

    def test_partial_tree_without_obs_module_is_quiet(self, tmp_path):
        f = findings_for(tmp_path, {"frame/mod.py": """
            from ..utils.profiling import counters

            def flush():
                counters.increment("whatever")
            """}, ["metric-name"])
        assert f == []


# ---------------------------------------------------------------------------
# the tier-1 gate: whole tree clean through the CLI
# ---------------------------------------------------------------------------

SCRIPT = os.path.join(REPO, "scripts", "check_static.py")


class TestCheckStaticGate:
    def test_whole_tree_is_clean(self):
        p = subprocess.run([sys.executable, SCRIPT, REPO],
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stdout + p.stderr
        assert "dqlint clean" in p.stdout

    def test_cli_flags_offender_tree(self, tmp_path):
        tree(tmp_path, OFFENDER_HOST_SYNC)
        p = subprocess.run([sys.executable, SCRIPT, str(tmp_path)],
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 1
        assert "[host-sync]" in p.stdout

    def test_cli_json_and_baseline_update(self, tmp_path):
        tree(tmp_path, OFFENDER_HOST_SYNC)
        bl = str(tmp_path / "bl.json")
        p = subprocess.run([sys.executable, SCRIPT, str(tmp_path),
                            "--baseline", bl, "--update-baseline"],
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stdout + p.stderr
        assert json.load(open(bl))["entries"]
        # baselined now: gate passes but findings render as baselined
        p = subprocess.run([sys.executable, SCRIPT, str(tmp_path),
                            "--baseline", bl, "--json"],
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0
        doc = json.loads(p.stdout)
        assert doc["findings"] and all(f["baselined"]
                                       for f in doc["findings"])

    def test_list_rules_catalog(self):
        p = subprocess.run([sys.executable, SCRIPT, "--list-rules"],
                           capture_output=True, text=True, timeout=60)
        assert p.returncode == 0
        for name in ("host-sync", "collective-guard", "conf-key", "noop",
                     "lock-order", "fault-site", "metric-name",
                     "logger-ns", "numpy-free"):
            assert name in p.stdout
