"""Hot-path/API lint: rule unit tests on snippets + the clean-tree gate."""
import os
import textwrap

from repro.analysis.lint import lint_paths, lint_source

_SRC = os.path.join(os.path.dirname(__file__), "..", "src", "repro")


def _codes(src, **kw):
    return [f.code for f in lint_source(textwrap.dedent(src), **kw)]


# --------------------------------------------------------------------------
# public-assert
# --------------------------------------------------------------------------
def test_assert_on_public_function_flagged():
    assert _codes("""
        def api(x):
            assert x > 0
    """) == ["public-assert"]


def test_assert_in_private_helper_allowed():
    assert _codes("""
        def _helper(x):
            assert x > 0
    """) == []


def test_assert_in_nested_private_scope_allowed():
    assert _codes("""
        class Engine:
            def _step(self, x):
                assert x > 0
    """) == []


def test_assert_in_dunder_is_public():
    assert _codes("""
        class Engine:
            def __init__(self, x):
                assert x > 0
    """) == ["public-assert"]


def test_module_level_assert_flagged():
    assert _codes("assert True\n") == ["public-assert"]


# --------------------------------------------------------------------------
# metric-name
# --------------------------------------------------------------------------
def test_conforming_metric_name_passes():
    assert _codes("""
        m.counter("repro.serve.requests").inc()
        m.histogram("repro.tune.cache.load_s").observe(1.0)
    """) == []


def test_nonconforming_metric_names_flagged():
    assert _codes("""
        m.counter("requests").inc()
        m.gauge("repro.queueDepth").set(1)
    """) == ["metric-name", "metric-name"]


def test_dynamic_metric_name_not_checked():
    assert _codes("m.counter(name).inc()\n") == []


# --------------------------------------------------------------------------
# hot-path-alloc
# --------------------------------------------------------------------------
def test_allocation_in_disabled_path_flagged():
    # span args are evaluated before the span knows nothing records
    assert _codes("""
        def _dispatch(self, group):
            with self.tracer.span("repro.serve.dispatch", tags=[1, 2],
                                  what=f"{len(group)}"):
                pass
    """, hot_path=True) == ["hot-path-alloc", "hot-path-alloc"]


def test_stray_call_and_lock_in_disabled_path_flagged():
    found = _codes("""
        def _dispatch(self, group, extra):
            with self.tracer.span("repro.serve.dispatch", n=len(group),
                                  head=group[0], **extra) as sp:
                pass
    """, hot_path=True)
    assert found == ["hot-path-alloc"] * 3


def test_allowlisted_publish_in_disabled_path_passes():
    # literals, names and attribute loads are free; computed args go on
    # the live span
    assert _codes("""
        def _prewarm(self, fam, stage):
            with self.tracer.span("repro.serve.prewarm", layer=fam.layer,
                                  stage=stage, cold=True) as sp:
                if sp:
                    sp.set(n=len(fam.buckets), tags=[1, 2])
    """, hot_path=True) == []


def test_unguarded_branch_not_checked():
    # outside the serving/plan layers span args are not checked
    assert _codes("""
        def _tune(self, scene):
            with self.tracer.span("repro.tune.scene", scene=scene.describe()):
                pass
    """) == []


# --------------------------------------------------------------------------
# bare-except
# --------------------------------------------------------------------------
def test_bare_except_flagged_everywhere():
    assert _codes("""
        def _f():
            try:
                pass
            except:
                pass
    """) == ["bare-except"]


def test_broad_except_unguarded_module_ok():
    src = """
        def _f():
            try:
                pass
            except Exception:
                pass
    """
    assert _codes(src) == []
    assert _codes(src, guarded_except=True) == ["bare-except"]


def test_guarded_broad_except_with_noqa_or_reraise_ok():
    assert _codes("""
        def _f():
            try:
                pass
            except Exception:  # noqa: BLE001 — reviewed swallow
                pass
    """, guarded_except=True) == []
    assert _codes("""
        def _f():
            try:
                pass
            except BaseException:
                cleanup()
                raise
    """, guarded_except=True) == []


def test_syntax_error_reported_not_raised():
    assert _codes("def f(:\n") == ["syntax-error"]


# --------------------------------------------------------------------------
# the gate: the shipped tree is clean
# --------------------------------------------------------------------------
def test_src_tree_is_lint_clean():
    assert lint_paths(_SRC) == []
