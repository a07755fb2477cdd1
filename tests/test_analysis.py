"""simlint unit tests: one known-bad and one known-good snippet per
rule, inline suppression, baseline round-trip/diff, the CLI, and the
repo-clean gate (``src/repro`` must scan clean at HEAD)."""

import pathlib
import textwrap

import pytest

from repro.analysis import check_paths, check_source
from repro.analysis.baseline import (
    diff_baseline,
    load_baseline,
    write_baseline,
)
from repro.analysis.cli import main as cli_main
from repro.analysis.engine import iter_py_files
from repro.analysis.rules import all_rules, rules_by_id

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


def findings_for(rule_id: str, source: str, rel: str = "core/snippet.py"):
    rules = rules_by_id([rule_id])
    return check_source(textwrap.dedent(source), rules=rules, rel=rel)


def assert_flags(rule_id: str, source: str, rel: str = "core/snippet.py"):
    found = findings_for(rule_id, source, rel)
    assert found, f"{rule_id} missed a known-bad snippet"
    assert all(f.rule == rule_id for f in found)
    return found


def assert_clean(rule_id: str, source: str, rel: str = "core/snippet.py"):
    found = findings_for(rule_id, source, rel)
    assert not found, f"{rule_id} false positive: {[f.render() for f in found]}"


# -- one known-bad (and one known-good) snippet per rule -----------------


class TestSL101UnseededRandom:
    def test_flags_global_rng(self):
        assert_flags("SL101", """
            import numpy as np
            x = np.random.uniform(0, 1, 100)
        """)

    def test_allows_default_rng(self):
        assert_clean("SL101", """
            import numpy as np
            rng = np.random.default_rng(0)
            seq = np.random.SeedSequence([1, 2])
            x = rng.uniform(0, 1, 100)
        """)


class TestSL102UnscopedX64:
    def test_flags_config_update(self):
        assert_flags("SL102", """
            import jax
            jax.config.update("jax_enable_x64", True)
        """)

    def test_flags_unscoped_enable_call(self):
        assert_flags("SL102", """
            import jax
            jax.enable_x64(True)
        """)
        assert_flags("SL102", """
            from repro.runtime import x64
            x64()
        """)

    def test_allows_scoped_context(self):
        assert_clean("SL102", """
            from repro.runtime import x64
            with x64():
                pass
        """)

    def test_flags_enable_outside_helper(self):
        source = """
            import jax
            with jax.enable_x64(True):
                pass
        """
        assert_flags("SL102", source)
        assert_clean("SL102", source, rel="repro/runtime.py")


class TestSL103TracedBranch:
    def test_flags_if_on_jitted_param(self):
        assert_flags("SL103", """
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """)

    def test_flags_branch_in_scanned_fn(self):
        assert_flags("SL103", """
            from jax import lax

            def step(carry, ev):
                if ev:
                    carry = carry + 1
                return carry, None

            out = lax.scan(step, 0, xs)
        """)

    def test_allows_static_argnames(self):
        assert_clean("SL103", """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("mode",))
            def f(x, mode):
                if mode:
                    return x
                return -x
        """)

    def test_allows_lax_cond(self):
        assert_clean("SL103", """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return jnp.where(x > 0, x, -x)
        """)


class TestSL104UnorderedIteration:
    def test_flags_for_over_set_literal(self):
        assert_flags("SL104", """
            for node in {3, 1, 2}:
                emit(node)
        """)

    def test_flags_list_of_set_call(self):
        assert_flags("SL104", """
            order = list(set(xs))
        """)

    def test_allows_sorted_set(self):
        assert_clean("SL104", """
            for node in sorted({3, 1, 2}):
                emit(node)
            order = sorted(set(xs))
        """)


class TestSL105TapeColumnMutation:
    def test_flags_subscript_store(self):
        assert_flags("SL105", """
            def f(batch):
                batch.sizes[0] = 0
        """)

    def test_flags_inplace_sort(self):
        assert_flags("SL105", """
            def f(scores):
                scores.percentage.sort()
        """)

    def test_allows_copy_then_mutate(self):
        assert_clean("SL105", """
            def f(batch):
                sizes = batch.sizes.copy()
                sizes[0] = 0
                srt = np.sort(scores.percentage)
        """)


class TestSL106LoadBearingAssert:
    def test_flags_assert(self):
        assert_flags("SL106", """
            def f(pipeline):
                assert pipeline.flush_job is not None
        """)

    def test_allows_raise(self):
        assert_clean("SL106", """
            def f(pipeline):
                if pipeline.flush_job is None:
                    raise RuntimeError("no active flush job")
        """)


class TestSL107UnitSuffix:
    def test_flags_cross_family_assign(self):
        assert_flags("SL107", """
            total_bytes = elapsed_seconds
        """)

    def test_flags_cross_family_add(self):
        assert_flags("SL107", """
            budget = wait_seconds + backlog_bytes
        """)

    def test_allows_same_family_and_converted(self):
        assert_clean("SL107", """
            total_bytes = region_bytes + overflow_bytes
            wall_seconds = io_seconds + gap_seconds
            total_mb = used_bytes / 1e6
        """)


class TestSL108EngineContract:
    BAD = """
        def run_replay(trace):
            \"\"\"Replays the trace.\"\"\"
            return trace
    """

    def test_flags_core_entry_point_without_contract(self):
        assert_flags("SL108", self.BAD, rel="core/engine.py")

    def test_ignores_non_core_modules(self):
        assert_clean("SL108", self.BAD, rel="service/loop.py")

    def test_allows_documented_contract(self):
        assert_clean("SL108", """
            def run_replay(trace):
                \"\"\"Replay; bit-identical to the per-request oracle.\"\"\"
                return trace
        """, rel="core/engine.py")


class TestSL109MutableDefault:
    def test_flags_list_default(self):
        assert_flags("SL109", """
            def f(x, acc=[]):
                acc.append(x)
                return acc
        """)

    def test_allows_none_default(self):
        assert_clean("SL109", """
            def f(x, acc=None):
                acc = [] if acc is None else acc
                acc.append(x)
                return acc
        """)


class TestSL110SilentException:
    def test_flags_bare_except(self):
        assert_flags("SL110", """
            try:
                risky()
            except:
                pass
        """)

    def test_flags_swallowed_exception(self):
        assert_flags("SL110", """
            try:
                risky()
            except Exception:
                pass
        """)

    def test_allows_handled_exception(self):
        assert_clean("SL110", """
            try:
                risky()
            except ValueError:
                pass
            try:
                risky()
            except Exception as e:
                log(e)
        """)


class TestSL111MethodLruCache:
    def test_flags_cached_method(self):
        assert_flags("SL111", """
            import functools

            class Sim:
                @functools.lru_cache(maxsize=8)
                def score(self, n):
                    return n * n
        """)

    def test_allows_module_level_cache(self):
        assert_clean("SL111", """
            import functools

            @functools.lru_cache(maxsize=8)
            def score(n):
                return n * n

            class Sim:
                @staticmethod
                def helper(n):
                    return score(n)
        """)


# -- engine mechanics ----------------------------------------------------


def test_inline_suppression():
    src = "def f(x):\n    assert x  # simlint: disable=SL106\n"
    assert check_source(src, rules=rules_by_id(["SL106"])) == []
    # a different rule id does not suppress
    src2 = "def f(x):\n    assert x  # simlint: disable=SL101\n"
    assert len(check_source(src2, rules=rules_by_id(["SL106"]))) == 1


def test_suppress_all():
    src = "def f(x):\n    assert x  # simlint: disable=all\n"
    assert check_source(src) == []


def test_fingerprint_is_line_independent():
    a = check_source("def f(x):\n    assert x\n")
    b = check_source("\n\n\ndef f(x):\n    assert x\n")
    assert a[0].line != b[0].line
    assert a[0].fingerprint == b[0].fingerprint


def test_rules_by_id_rejects_unknown():
    with pytest.raises(ValueError, match="unknown rule"):
        rules_by_id(["SL999"])


def test_registry_has_at_least_eight_distinct_rules():
    ids = [r.id for r in all_rules()]
    assert len(ids) == len(set(ids))
    assert len(ids) >= 8


def test_iter_py_files_rejects_non_python(tmp_path):
    f = tmp_path / "data.json"
    f.write_text("{}")
    with pytest.raises(ValueError, match="not a .py file"):
        iter_py_files([f])


# -- baseline ------------------------------------------------------------


def test_baseline_round_trip_and_diff(tmp_path):
    findings = check_source("def f(x):\n    assert x\n    assert not x\n")
    assert len(findings) == 2
    path = tmp_path / "baseline.json"
    write_baseline(path, findings)
    counts = load_baseline(path)
    assert sum(counts.values()) == 2

    # same findings: nothing new, nothing stale
    new, stale = diff_baseline(findings, counts)
    assert new == [] and stale == []

    # one fixed: it shows up as stale
    new, stale = diff_baseline(findings[:1], counts)
    assert new == [] and len(stale) == 1

    # a fresh finding is reported as new
    extra = check_source("def g(y):\n    assert y\n")
    new, stale = diff_baseline(findings + extra, counts)
    assert len(new) == 1


def test_baseline_rejects_bad_schema(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text('{"schema": "nope/v9", "fingerprints": {}}')
    with pytest.raises(ValueError, match="unknown baseline schema"):
        load_baseline(path)


# -- CLI -----------------------------------------------------------------


def test_cli_check_and_baseline_flow(tmp_path, capsys):
    bad = tmp_path / "mod.py"
    bad.write_text("def f(x):\n    assert x\n")

    assert cli_main(["--check", str(bad)]) == 1
    assert "SL106" in capsys.readouterr().out

    baseline = tmp_path / "baseline.json"
    assert cli_main(
        ["--check", str(bad), "--write-baseline", str(baseline)]
    ) == 0
    capsys.readouterr()
    # baselined: clean exit
    assert cli_main(["--check", str(bad), "--baseline", str(baseline)]) == 0
    assert "clean" in capsys.readouterr().out

    # fixing the file makes the baseline entry stale -> nonzero, so the
    # baseline cannot rot silently
    bad.write_text("def f(x):\n    return x\n")
    assert cli_main(["--check", str(bad), "--baseline", str(baseline)]) == 1
    assert "stale" in capsys.readouterr().out


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "SL106" in out and "load-bearing-assert" in out


def test_cli_requires_check(capsys):
    assert cli_main([]) == 2


# -- the gate: the repo itself scans clean -------------------------------


def test_src_repro_is_simlint_clean():
    findings = check_paths([SRC])
    assert findings == [], "\n".join(f.render() for f in findings)
