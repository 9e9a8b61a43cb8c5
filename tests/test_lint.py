"""graftlint self-tests: every rule fires on its fixture, suppressions with
reasons are honored, malformed directives are findings, and the real
package is clean.

The fixture tree under tests/fixtures/graftlint/pkg mimics the package
layout (ops/, treelearner/) so path-scoped rules apply to it unchanged.
"""
import subprocess
import sys
from pathlib import Path

import pytest

from tools.graftlint import run_lint, rule_codes

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "graftlint" / "pkg"
PACKAGE = REPO / "lightgbm_tpu"


@pytest.fixture(scope="module")
def fixture_result():
    return run_lint(FIXTURES)


def _hits(result, rule, path=None, suppressed=False):
    pool = result.suppressed if suppressed else result.violations
    return [v for v in pool
            if v.rule == rule and (path is None or v.path == path)]


# -- R1 jit-boundary hygiene ---------------------------------------------

def test_r1_detects_host_syncs(fixture_result):
    lines = {v.line for v in _hits(fixture_result, "jit-host-sync",
                                   "ops/r1_jit.py")}
    assert lines == {9, 15, 16}  # int(tracer), .item(), np.asarray


def test_r1_static_and_unreachable_are_clean(fixture_result):
    # int(x.shape[0]) (line 17) and the non-jit-reachable int(x) (line 24)
    # must not fire
    lines = {v.line for v in _hits(fixture_result, "jit-host-sync")}
    assert 17 not in lines and 24 not in lines


def test_r1_suppression_honored(fixture_result):
    sup = _hits(fixture_result, "jit-host-sync", "ops/r1_jit.py",
                suppressed=True)
    assert [v.line for v in sup] == [19]
    assert "host-side by contract" in sup[0].reason


def test_r1_loop_sync_on_fresh_dispatch(fixture_result):
    # np.asarray(predict_block(x)) per loop iteration — the pre-rewrite
    # predict_raw_early_stop shape — must fire with the pipeline message
    bad = _hits(fixture_result, "jit-host-sync", "ops/r1_stream.py")
    assert [v.line for v in bad] == [18]
    assert "serializes the dispatch pipeline" in bad[0].message


def test_r1_loop_sync_buffered_and_suppressed(fixture_result):
    # pulling a PREVIOUSLY dispatched value (bare name, double-buffer
    # drain) is clean; the reasoned suppression is honored
    sup = _hits(fixture_result, "jit-host-sync", "ops/r1_stream.py",
                suppressed=True)
    assert [v.line for v in sup] == [36]
    assert "tiny scalar pull" in sup[0].reason


# -- R2 dtype discipline --------------------------------------------------

def test_r2_detects_implicit_dtype(fixture_result):
    lines = {v.line for v in _hits(fixture_result, "implicit-dtype",
                                   "ops/r2_dtype.py")}
    assert lines == {6, 7}  # bare zeros + arange


def test_r2_explicit_and_like_are_clean(fixture_result):
    # dtype kwarg (8), positional dtype slot (9), zeros_like (10)
    lines = {v.line for v in _hits(fixture_result, "implicit-dtype")}
    assert not lines & {8, 9, 10}


def test_r2_family_code_suppression(fixture_result):
    sup = _hits(fixture_result, "implicit-dtype", "ops/r2_dtype.py",
                suppressed=True)
    assert [v.line for v in sup] == [11]  # disable=R2 covers the rule


# -- R3 Pallas kernel rules -----------------------------------------------

def test_r3_tile_shape_resolves_module_constants(fixture_result):
    msgs = [v.message for v in _hits(fixture_result, "pallas-tile-shape",
                                     "ops/r3_pallas.py")]
    # TILE = 100 resolved symbolically -> both sublane and lane misaligned
    assert len(msgs) == 2
    assert any("multiple of 128" in m for m in msgs)
    assert any("multiple of 8" in m for m in msgs)


def test_r3_prefetch_arity(fixture_result):
    bad = _hits(fixture_result, "pallas-prefetch-arity", "ops/r3_pallas.py")
    assert len(bad) == 1 and "takes 2 args, expected 1" in bad[0].message
    sup = _hits(fixture_result, "pallas-prefetch-arity", "ops/r3_pallas.py",
                suppressed=True)
    # num_scalar_prefetch=1 shifts the expected arity; disable=R3 covers it
    assert len(sup) == 1 and "expected 2" in sup[0].message


def test_r3_host_op_in_kernel(fixture_result):
    bad = _hits(fixture_result, "pallas-host-op", "ops/r3_pallas.py")
    assert [v.line for v in bad] == [11]  # np.asarray in kernel body
    sup = _hits(fixture_result, "pallas-host-op", "ops/r3_pallas.py",
                suppressed=True)
    assert [v.line for v in sup] == [13]  # suppressed print()


# -- R4 param-spec consistency --------------------------------------------

def test_r4_unread_param_detected(fixture_result):
    bad = _hits(fixture_result, "param-unread", "_param_spec.py")
    assert len(bad) == 1 and "'ghost_param'" in bad[0].message


def test_r4_read_param_clean_and_suppression_honored(fixture_result):
    all_msgs = [v.message for v in
                fixture_result.violations + fixture_result.suppressed]
    assert not any("'used_param'" in m for m in all_msgs)
    sup = _hits(fixture_result, "param-unread", suppressed=True)
    assert len(sup) == 1 and "'surface_param'" in sup[0].message


# -- R5 timer discipline --------------------------------------------------

def test_r5_untimed_long_function(fixture_result):
    bad = _hits(fixture_result, "untimed-hot-func", "treelearner/r5_big.py")
    assert len(bad) == 1 and "'big_untimed'" in bad[0].message


def test_r5_timed_and_jitted_exempt(fixture_result):
    msgs = [v.message for v in
            fixture_result.violations + fixture_result.suppressed]
    assert not any("'big_timed'" in m for m in msgs)
    assert not any("'big_jitted'" in m for m in msgs)


def test_r5_scope_covers_serving_hot_path(fixture_result):
    # ops/predict.py joined the R5 scope (scope_exact): the untimed pack
    # helper fixture must fire there too
    bad = _hits(fixture_result, "untimed-hot-func", "ops/predict.py")
    assert len(bad) == 1 and "'big_untimed_pack'" in bad[0].message


def test_r5_scope_covers_fused_scan(fixture_result, monkeypatch):
    # a file outside the rule's prefixes is in R5's scope exactly when
    # scope_exact names it. The fixture's ops/scan_pallas.py (the package's
    # own went with its kernel, PR 30) is silent until it is named; then
    # the untimed staging helper fires at its def line and the jitted
    # dispatch stays exempt (the call site owns the scope, device.py's
    # "tree_device")
    from tools.graftlint.rules.timer_discipline import TimerDisciplineRule

    assert _hits(fixture_result, "untimed-hot-func",
                 "ops/scan_pallas.py") == []
    monkeypatch.setattr(
        TimerDisciplineRule, "scope_exact",
        TimerDisciplineRule.scope_exact + ("ops/scan_pallas.py",))
    result = run_lint(FIXTURES)
    bad = _hits(result, "untimed-hot-func", "ops/scan_pallas.py")
    assert len(bad) == 1 and "'big_untimed_stage'" in bad[0].message
    assert bad[0].line == 7
    msgs = [v.message for v in result.violations + result.suppressed]
    assert not any("'big_jitted_scan'" in m for m in msgs)


def test_r5_suppression_honored(fixture_result):
    sup = _hits(fixture_result, "untimed-hot-func", suppressed=True)
    assert len(sup) == 1 and "'big_suppressed'" in sup[0].message


# -- R6 donation discipline -----------------------------------------------

def test_r6_undonated_jit_entry_detected(fixture_result):
    bad = _hits(fixture_result, "jit-donation", "treelearner/r6_donate.py")
    assert len(bad) == 1 and "'undonated'" in bad[0].message
    assert bad[0].line == 8  # anchored at the decorator, not the def


def test_r6_donated_scalar_and_unjitted_are_clean(fixture_result):
    msgs = [v.message for v in
            fixture_result.violations + fixture_result.suppressed]
    for name in ("'donated'", "'scalar_only'", "'not_jitted'"):
        assert not any(name in m and "donate" in m for m in msgs), name


def test_r6_suppression_honored(fixture_result):
    sup = _hits(fixture_result, "jit-donation", "treelearner/r6_donate.py",
                suppressed=True)
    assert len(sup) == 1 and "'suppressed'" in sup[0].message
    assert "reused across iterations" in sup[0].reason


# -- R7 collective axis binding -------------------------------------------

def test_r7_unbound_collectives_detected(fixture_result):
    bad = _hits(fixture_result, "collective-axis", "parallel/r7_axis.py")
    msgs = {v.line: v.message for v in bad}
    assert set(msgs) == {22, 26, 30, 34}
    assert "'batch'" in msgs[22]       # axis not bound anywhere
    assert "no shard_map" in msgs[26]  # function never wrapped
    assert "not a string literal" in msgs[30]
    assert "without an axis name" in msgs[34]


def test_r7_wrapped_chain_and_nested_are_clean(fixture_result):
    # psum/psum_scatter reached from shard_map-wrapped fns (directly, via a
    # module call edge, and from a nested def) must not fire
    lines = {v.line for v in
             _hits(fixture_result, "collective-axis", "parallel/r7_axis.py")}
    assert not lines & {8, 12, 44}


def test_r7_suppression_honored(fixture_result):
    sup = _hits(fixture_result, "collective-axis", suppressed=True)
    assert len(sup) == 1 and "bound by the caller's shard_map" in sup[0].reason


# -- R8 atomic-write discipline -------------------------------------------

def test_r8_bare_write_opens_detected(fixture_result):
    bad = _hits(fixture_result, "non-atomic-write", "models/r8_write.py")
    assert [v.line for v in bad] == [5, 10]  # positional + mode= keyword
    assert all("atomic" in v.message for v in bad)


def test_r8_reads_and_dynamic_modes_are_clean(fixture_result):
    lines = {v.line for v in
             _hits(fixture_result, "non-atomic-write", "models/r8_write.py")
             + _hits(fixture_result, "non-atomic-write", "models/r8_write.py",
                     suppressed=True)}
    assert not lines & {15, 20, 25}


def test_r8_suppression_honored(fixture_result):
    sup = _hits(fixture_result, "non-atomic-write", suppressed=True)
    assert len(sup) == 1 and "scratch debug dump" in sup[0].reason


# -- R9 telemetry hygiene -------------------------------------------------

def test_r9_unguarded_emit_detected(fixture_result):
    bad = _hits(fixture_result, "telemetry-hygiene",
                "treelearner/r9_telemetry.py")
    assert [v.line for v in bad] == [7]
    assert "enabled" in bad[0].message


def test_r9_guards_counters_and_foreign_emit_are_clean(fixture_result):
    lines = {v.line for v in
             _hits(fixture_result, "telemetry-hygiene")
             + _hits(fixture_result, "telemetry-hygiene", suppressed=True)}
    # if-guard (13), ternary guard (18), counter API (23), handler.emit (27)
    assert not lines & {13, 18, 23, 27}


def test_r9_suppression_honored(fixture_result):
    sup = _hits(fixture_result, "telemetry-hygiene", suppressed=True)
    assert len(sup) == 1 and "cold error path" in sup[0].reason


def test_r9_tracing_scope_exact(fixture_result):
    # tracing.py is in scope_exact: an unguarded telemetry.emit there
    # fires even though the file sits outside the scoped directories
    bad = _hits(fixture_result, "telemetry-hygiene", "tracing.py")
    assert [v.line for v in bad] == [12]


def test_r9_recorder_append_is_sanctioned(fixture_result):
    # the flight-recorder ring append (note()) and the cold dump path's
    # foreign sink.emit must NOT trip R9 — only telemetry.emit needs a
    # guard; the guarded emit (line 18) is clean too
    lines = {v.line for v in
             _hits(fixture_result, "telemetry-hygiene", "tracing.py")
             + _hits(fixture_result, "telemetry-hygiene", "tracing.py",
                     suppressed=True)}
    assert not lines & {18, 25, 26, 32}


# -- streaming/ scope (R1/R6/R9/R10 cover the out-of-core engine) ---------

def test_streaming_scope_r1_and_r6(fixture_result):
    r6 = _hits(fixture_result, "jit-donation", "streaming/r_stream.py")
    assert [v.line for v in r6] == [10]
    assert "'block_hist'" in r6[0].message
    r1 = _hits(fixture_result, "jit-host-sync", "streaming/r_stream.py")
    assert [v.line for v in r1] == [12]


def test_streaming_scope_r9_and_r10(fixture_result):
    r10 = _hits(fixture_result, "use-after-donation",
                "streaming/r_stream.py")
    assert [v.line for v in r10] == [23]
    assert "'acc'" in r10[0].message
    r9 = _hits(fixture_result, "telemetry-hygiene", "streaming/r_stream.py")
    assert [v.line for v in r9] == [24, 43]


def test_streaming_clean_and_suppressed(fixture_result):
    # donated accum (17), rebound-name read (29), guarded emits (31, 50): clean
    lines = {v.line for v in
             fixture_result.violations + fixture_result.suppressed
             if v.path == "streaming/r_stream.py"}
    assert not lines & {17, 29, 31, 50}
    sup = _hits(fixture_result, "jit-donation", "streaming/r_stream.py",
                suppressed=True)
    assert len(sup) == 1 and "reused across leaves" in sup[0].reason


# -- parallel/elastic.py scope (R1 beat path + R9 watchdog emits) ---------

def test_elastic_scope_r9_watchdog_emit(fixture_result):
    # the watchdog fire path builds a worker_lost payload: unguarded emit
    # fires, the enabled-guarded twin stays clean
    r9 = _hits(fixture_result, "telemetry-hygiene", "parallel/elastic.py")
    assert [v.line for v in r9] == [15]


def test_elastic_scope_r1_per_iteration_heartbeat(fixture_result):
    # a heartbeat that pulls the token every iteration is exactly the
    # hot-path host sync the elastic runtime must NOT reintroduce
    r1 = _hits(fixture_result, "jit-host-sync", "parallel/elastic.py")
    assert [v.line for v in r1] == [21]
    assert "serializes the dispatch pipeline" in r1[0].message


def test_elastic_scope_windowed_pull_suppressed(fixture_result):
    # the sanctioned shape — one pull per health window — carries its
    # reasoned escape hatch; nothing else in the file may be suppressed
    sup = [v for v in fixture_result.suppressed
           if v.path == "parallel/elastic.py"]
    assert [(v.rule, v.line) for v in sup] == [("jit-host-sync", 30)]
    assert "health window" in sup[0].reason


# -- S1 directive hygiene -------------------------------------------------

def test_s1_bad_directives_are_findings(fixture_result):
    bad = _hits(fixture_result, "bad-suppression", "s1_bad.py")
    msgs = {v.line: v.message for v in bad}
    assert "without a reason" in msgs[2]
    assert "not-a-rule" in msgs[3]
    assert "unparseable" in msgs[4]


def test_s1_is_never_suppressible():
    # a reasoned disable=S1 on the same line must NOT silence the finding
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        p = Path(d) / "x.py"
        p.write_text("A = 1  # graftlint: disable=implicit-dtype\n")
        res = run_lint(p)
        assert [v.rule for v in res.violations] == ["bad-suppression"]


# -- driver behavior ------------------------------------------------------

def test_select_filters_rules(fixture_result):
    res = run_lint(FIXTURES, select=["R2"])
    rules = {v.rule for v in res.violations}
    # directive errors always surface; otherwise only the selected rule
    assert rules <= {"implicit-dtype", "bad-suppression"}
    assert "implicit-dtype" in rules


def test_ignore_filters_rules():
    res = run_lint(FIXTURES, ignore=["param-unread"])
    assert not any(v.rule == "param-unread" for v in res.violations)


def test_rule_codes_cover_names_and_codes():
    table = rule_codes()
    for ident in ("R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8", "R9",
                  "R10", "R11",
                  "jit-donation", "jit-host-sync", "jit-host-sync-xmod",
                  "implicit-dtype", "pallas-tile-shape",
                  "pallas-prefetch-arity", "pallas-host-op",
                  "param-unread", "untimed-hot-func", "collective-axis",
                  "non-atomic-write", "telemetry-hygiene",
                  "use-after-donation", "collective-context"):
        assert ident in table
    # two rules share the R1 code; the code must keep resolving to the
    # ORIGINAL local rule, with the family expansion covering both
    assert table["R1"] == "jit-host-sync"


def test_code_family_expansion_covers_both_r1_rules():
    from tools.graftlint.rules import code_families

    fams = code_families()
    assert {"jit-host-sync", "jit-host-sync-xmod"} <= set(fams["R1"])
    # selecting by code runs the whole family; ignoring by code drops it
    both = run_lint(FIXTURES, select=["R1"])
    assert any(v.rule == "jit-host-sync" for v in both.violations)
    none = run_lint(FIXTURES, ignore=["R1"])
    assert not any(v.rule.startswith("jit-host-sync")
                   for v in none.violations)


# -- the gate: the real package is clean ----------------------------------

def test_package_has_zero_unsuppressed_violations():
    res = run_lint(PACKAGE)
    assert res.ok, "\n" + res.render()


def test_every_package_suppression_carries_a_reason():
    res = run_lint(PACKAGE)
    assert all(v.reason for v in res.suppressed)


def test_cli_exit_codes():
    clean = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "lightgbm_tpu"],
        cwd=REPO, capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    dirty = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", str(FIXTURES)],
        cwd=REPO, capture_output=True, text=True)
    assert dirty.returncode == 1
    usage = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--select", "no-such-rule",
         "lightgbm_tpu"],
        cwd=REPO, capture_output=True, text=True)
    assert usage.returncode == 2
