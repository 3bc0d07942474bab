"""Tier-1 gate for the project static analyzer (ceph_tpu.analysis).

Three contracts:

* the shipped tree is clean: `python tools/lint.py` (ceph_tpu, tools)
  produces zero unsuppressed, unbaselined findings;
* every rule fires on its bad fixture and stays silent on its good
  fixture (tests/lint_fixtures/);
* the suppression layers round-trip: inline `# lint: disable=` and
  the baseline file each absorb exactly the findings they name.
"""

import os
import subprocess
import sys

import pytest

from ceph_tpu import analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
TREE_PATHS = ["ceph_tpu", "tools"]
BASELINE = os.path.join(REPO, "tools", "lint_baseline.txt")

RULE_FIXTURES = {
    "await-under-lock": ("osd/await_under_lock_bad.py",
                         "osd/await_under_lock_good.py"),
    "config-schema": ("config_schema_bad.py",
                      "config_schema_good.py"),
    "dropped-task": ("dropped_task_bad.py",
                     "dropped_task_good.py"),
    "hole-sentinel": ("hole_sentinel_bad.py",
                      "hole_sentinel_good.py"),
    "x64-scope": ("x64_scope_bad.py", "x64_scope_good.py"),
    "tracer-safety": ("ops/tracer_safety_bad.py",
                      "ops/tracer_safety_good.py"),
    "jit-stability": ("jit_stability_bad.py",
                      "jit_stability_good.py"),
    "perf-coherence": ("perf_coherence_bad.py",
                       "perf_coherence_good.py"),
    "blocking-under-lock": ("osd/blocking_under_lock_bad.py",
                            "osd/blocking_under_lock_good.py"),
    "device-path-host-sync": ("device_path_bad.py",
                              "device_path_good.py"),
    "donated-buffer-aliasing": ("donated_aliasing_bad.py",
                                "donated_aliasing_good.py"),
    "denc-symmetry": ("denc_symmetry_bad.py",
                      "denc_symmetry_good.py"),
    "lock-order": ("osd/lock_order_bad.py",
                   "osd/lock_order_good.py"),
    "counter-coverage": ("counter_coverage_bad.py",
                         "counter_coverage_good.py"),
    "hot-path-config-read": ("hot_config_bad.py",
                             "hot_config_good.py"),
    "cross-daemon-state": ("cross_daemon_state_bad.py",
                           "cross_daemon_state_good.py"),
    "wire-safety": ("wire_safety_bad.py",
                    "wire_safety_good.py"),
    "await-invalidates-snapshot": ("osd/await_snapshot_bad.py",
                                   "osd/await_snapshot_good.py"),
}


def lint(paths, root, rules=None, baseline=None):
    findings, project = analysis.run(paths, root=root, rules=rules)
    kept, n_inline, n_base = analysis.filter_suppressed(
        findings, project, baseline or set())
    return kept, n_inline, n_base


# -- the acceptance gate ----------------------------------------------------

def test_tree_is_clean():
    baseline = analysis.load_baseline(BASELINE)
    kept, _, _ = lint(TREE_PATHS, REPO, baseline=baseline)
    assert kept == [], "\n".join(f.render() for f in kept)


def test_all_rules_registered():
    names = {c.name for c in analysis.get_checkers()}
    assert set(RULE_FIXTURES) <= names


# -- per-rule fixture corpus ------------------------------------------------

@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_fires_on_bad_fixture(rule):
    bad, _ = RULE_FIXTURES[rule]
    kept, _, _ = lint([bad], FIXTURES, rules=[rule])
    assert kept, f"{rule} found nothing in {bad}"
    assert all(f.rule == rule for f in kept)


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_silent_on_good_fixture(rule):
    _, good = RULE_FIXTURES[rule]
    kept, _, _ = lint([good], FIXTURES, rules=[rule])
    assert kept == [], "\n".join(f.render() for f in kept)


def test_bad_fixtures_do_not_cross_fire():
    """Each bad fixture trips only its own rule (rule independence)."""
    for rule, (bad, _) in RULE_FIXTURES.items():
        kept, _, _ = lint([bad], FIXTURES)
        assert kept and {f.rule for f in kept} == {rule}, (
            rule, [f.render() for f in kept])


# -- interprocedural acceptance pins ----------------------------------------

def test_device_path_injection_two_calls_deep(tmp_path):
    """A host sync injected two calls deep (and one module away) from
    a launch entry point is found -- the static closure reaches where
    the per-module framework could not."""
    _write(tmp_path, "launch.py",
           "import numpy as np\n"
           "from helpers import stage1\n\n\n"
           "class CodecBatcher:\n"
           "    def encode(self, codec, arr):\n"
           "        return stage1(codec, np.ascontiguousarray(arr))\n")
    _write(tmp_path, "helpers.py",
           "import numpy as np\n\n\n"
           "def stage1(codec, arr):\n"
           "    return _stage2(codec.encode_batch(arr))\n\n\n"
           "def _stage2(out):\n"
           "    return np.asarray(out)\n")
    kept, _, _ = lint(["launch.py", "helpers.py"], str(tmp_path),
                      rules=["device-path-host-sync"])
    assert len(kept) == 1, [f.render() for f in kept]
    f = kept[0]
    assert f.path == "helpers.py"
    assert "CodecBatcher.encode" in f.message


def test_sched_executor_host_sync_flagged(tmp_path):
    """A host sync hiding inside the XOR-schedule executor is found:
    the scheduled-kernel entry points are device-path ROOTS, so the
    closure walks into their helpers like any other launch path."""
    _write(tmp_path, "xsched.py",
           "import numpy as np\n"
           "import jax.numpy as jnp\n\n\n"
           "def sched_matmul_batch_device(sched, matrix, xd, b, k, l):\n"
           "    return _run_ops(sched, xd)\n\n\n"
           "def _run_ops(sched, xd):\n"
           "    rows = np.asarray(xd)      # the smuggled host hop\n"
           "    return rows\n")
    kept, _, _ = lint(["xsched.py"], str(tmp_path),
                      rules=["device-path-host-sync"])
    assert len(kept) == 1, [f.render() for f in kept]
    assert kept[0].path == "xsched.py"
    assert "sched_matmul_batch_device" in kept[0].message


def test_donated_roots_flag_sched_launch_reuse(tmp_path, monkeypatch):
    """The donated-aliasing ROOTS seed a launch wrapper as a donor: a
    device buffer read after being fed into it is a use-after-donate
    finding, even though the jit carrying donate_argnums never appears
    in the AST.  Without the root the same file is clean."""
    from ceph_tpu.analysis.checkers import donated_aliasing
    _write(tmp_path, "meshy.py",
           "import jax\n\n\n"
           "class MeshCodec:\n"
           "    def _sched_launch(self, fn, dev_batch):\n"
           "        return fn(dev_batch)\n\n"
           "    def encode(self, fn, dev):\n"
           "        out = self._sched_launch(fn, dev)\n"
           "        return out, dev.sum()   # read-after-donate\n")
    kept, _, _ = lint(["meshy.py"], str(tmp_path),
                      rules=["donated-buffer-aliasing"])
    assert kept == []
    monkeypatch.setattr(donated_aliasing, "ROOTS",
                        (("MeshCodec._sched_launch", (1,)),))
    kept, _, _ = lint(["meshy.py"], str(tmp_path),
                      rules=["donated-buffer-aliasing"])
    assert len(kept) == 1, [f.render() for f in kept]
    assert "dev" in kept[0].message


def test_device_path_roots_cover_the_dynamic_gate():
    """Every launch entry point the scalar_calls_on_batched_paths
    bench gate drives resolves to a real function, so the static rule
    anchors at (at least) the paths the dynamic gate watches; so does
    every declared donor root of donated-buffer-aliasing."""
    from ceph_tpu.analysis.checkers.device_path import ROOTS
    from ceph_tpu.analysis.checkers.donated_aliasing import \
        ROOTS as DONOR_ROOTS
    _, project = analysis.run(TREE_PATHS, REPO,
                              rules=["device-path-host-sync"])
    graph = project.graph()
    missing = [spec for spec in ROOTS + tuple(s for s, _ in DONOR_ROOTS)
               if not graph.lookup(spec)]
    assert missing == [], missing


LINT_BUDGET_SECONDS = 30.0


def test_full_tree_lint_within_time_budget():
    """The whole-tree run -- parse, call graph, every rule -- must
    stay affordable or the pre-commit gate rots.  The budget is ~5x
    the current cost; a regression past it means something went
    accidentally quadratic."""
    import time
    t0 = time.perf_counter()
    profile = {}
    analysis.run(TREE_PATHS, REPO, profile=profile)
    elapsed = time.perf_counter() - t0
    assert elapsed < LINT_BUDGET_SECONDS, (
        f"full-tree lint took {elapsed:.1f}s "
        f"(budget {LINT_BUDGET_SECONDS}s); slowest rules: "
        f"{sorted(profile.items(), key=lambda kv: -kv[1])[:5]}")
    assert "[parse]" in profile and "[callgraph]" in profile


# -- suppression round-trips ------------------------------------------------

BAD_SNIPPET = 'import jax\njax.config.update("jax_enable_x64", True)\n'


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_text(text)
    return str(p)


def test_inline_suppression_same_line(tmp_path):
    _write(tmp_path, "mod.py", BAD_SNIPPET.replace(
        "True)", "True)  # lint: disable=x64-scope"))
    kept, n_inline, _ = lint(["mod.py"], str(tmp_path))
    assert kept == [] and n_inline == 1


def test_inline_suppression_standalone_line_above(tmp_path):
    _write(tmp_path, "mod.py",
           "import jax\n# lint: disable=x64-scope\n"
           'jax.config.update("jax_enable_x64", True)\n')
    kept, n_inline, _ = lint(["mod.py"], str(tmp_path))
    assert kept == [] and n_inline == 1


def test_inline_suppression_wrong_rule_does_not_apply(tmp_path):
    _write(tmp_path, "mod.py", BAD_SNIPPET.replace(
        "True)", "True)  # lint: disable=hole-sentinel"))
    kept, n_inline, _ = lint(["mod.py"], str(tmp_path))
    assert len(kept) == 1 and n_inline == 0


def test_inline_suppression_bare_disable_suppresses_all(tmp_path):
    _write(tmp_path, "mod.py", BAD_SNIPPET.replace(
        "True)", "True)  # lint: disable"))
    kept, n_inline, _ = lint(["mod.py"], str(tmp_path))
    assert kept == [] and n_inline == 1


def test_baseline_roundtrip(tmp_path):
    _write(tmp_path, "mod.py", BAD_SNIPPET)
    kept, _, _ = lint(["mod.py"], str(tmp_path))
    assert len(kept) == 1
    bl_path = str(tmp_path / "baseline.txt")
    analysis.write_baseline(bl_path, kept)
    baseline = analysis.load_baseline(bl_path)
    kept2, _, n_base = lint(["mod.py"], str(tmp_path),
                            baseline=baseline)
    assert kept2 == [] and n_base == 1
    # baseline keys are line-number free: an unrelated edit above the
    # finding must not resurrect it
    _write(tmp_path, "mod.py", "import os  # noqa\n" + BAD_SNIPPET)
    kept3, _, n_base3 = lint(["mod.py"], str(tmp_path),
                             baseline=baseline)
    assert kept3 == [] and n_base3 == 1


def test_inline_suppression_project_rule(tmp_path):
    """The suppression layers absorb interprocedural findings the
    same way they absorb per-module ones."""
    _write(tmp_path, "driver.py",
           "def probe(mon):\n"
           "    # lint: disable=cross-daemon-state -- test shortcut\n"
           "    return mon._stopped\n")
    kept, n_inline, _ = lint(["driver.py"], str(tmp_path))
    assert kept == [] and n_inline == 1


def test_baseline_roundtrip_project_rule(tmp_path):
    _write(tmp_path, "driver.py",
           "def probe(mon):\n    return mon._stopped\n")
    kept, _, _ = lint(["driver.py"], str(tmp_path))
    assert len(kept) == 1
    assert kept[0].rule == "cross-daemon-state"
    bl_path = str(tmp_path / "baseline.txt")
    analysis.write_baseline(bl_path, kept)
    baseline = analysis.load_baseline(bl_path)
    kept2, _, n_base = lint(["driver.py"], str(tmp_path),
                            baseline=baseline)
    assert kept2 == [] and n_base == 1


def test_await_snapshot_suppression_roundtrip(tmp_path):
    """await-invalidates-snapshot honors the standalone-line-above
    directive (how every in-tree justification is written)."""
    (tmp_path / "osd").mkdir()
    _write(tmp_path, "osd/loop.py",
           "import asyncio\n\nSTATE = {}\n\n\n"
           "async def tick(k):\n"
           "    v = STATE[k]\n"
           "    await asyncio.sleep(0)\n"
           "    # lint: disable=await-invalidates-snapshot -- why\n"
           "    return v\n")
    kept, n_inline, _ = lint(["osd/loop.py"], str(tmp_path))
    assert kept == [] and n_inline == 1


def test_syntax_error_is_a_parse_finding(tmp_path):
    _write(tmp_path, "mod.py", "def broken(:\n")
    kept, _, _ = lint(["mod.py"], str(tmp_path))
    assert len(kept) == 1 and kept[0].rule == "parse"


def test_unknown_rule_raises():
    with pytest.raises(KeyError):
        analysis.run(["hole_sentinel_bad.py"], root=FIXTURES,
                     rules=["no-such-rule"])


# -- CLI --------------------------------------------------------------------

def _cli(*argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py"),
         *argv],
        cwd=REPO, capture_output=True, text=True, timeout=120)


def test_cli_full_tree_exits_zero():
    res = _cli()
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.strip() == ""


def test_cli_list_rules_names_every_rule():
    res = _cli("--list-rules")
    assert res.returncode == 0
    for rule in RULE_FIXTURES:
        assert rule in res.stdout


def test_cli_nonzero_on_findings_and_rule_filter():
    bad = os.path.join("tests", "lint_fixtures",
                       "x64_scope_bad.py")
    res = _cli("--rules", "x64-scope", bad)
    assert res.returncode == 1
    assert "x64-scope" in res.stdout
    res2 = _cli("--rules", "hole-sentinel", bad)
    assert res2.returncode == 0


def test_cli_changed_mode_runs():
    """--changed lints the git-dirty files plus their reverse-
    reachable callers (never the fixture corpus), so it exits clean
    on a clean tree and on a tree whose dirty closure passes."""
    res = _cli("--changed")
    assert res.returncode == 0, res.stdout + res.stderr


def test_cli_profile_reports_per_rule_times():
    res = _cli("--profile", "ceph_tpu/analysis")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "[parse]" in res.stderr
    assert "[callgraph]" in res.stderr
    assert "[total]" in res.stderr
    assert "device-path-host-sync" in res.stderr
    for rule in ("cross-daemon-state", "wire-safety",
                 "await-invalidates-snapshot"):
        assert rule in res.stderr


def test_cli_format_json():
    import json
    bad = os.path.join("tests", "lint_fixtures", "x64_scope_bad.py")
    res = _cli("--rules", "x64-scope", "--format", "json", bad)
    assert res.returncode == 1
    data = json.loads(res.stdout)
    assert data and data[0]["rule"] == "x64-scope"
    assert {"path", "line", "rule", "message"} <= set(data[0])
    # a clean run emits an empty (but valid) document
    res2 = _cli("--format", "json", "ceph_tpu/common")
    assert res2.returncode == 0
    assert json.loads(res2.stdout) == []


def test_cli_format_sarif():
    import json
    bad = os.path.join("tests", "lint_fixtures", "x64_scope_bad.py")
    res = _cli("--rules", "x64-scope", "--format", "sarif", bad)
    assert res.returncode == 1
    doc = json.loads(res.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert {"id": "x64-scope"} in run["tool"]["driver"]["rules"]
    r = run["results"][0]
    assert r["ruleId"] == "x64-scope"
    loc = r["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith(
        "x64_scope_bad.py")
    assert loc["region"]["startLine"] >= 1


# -- the process-seam audit --------------------------------------------------

def test_seam_report_schema_and_nonemptiness():
    """The swarm PR's entry gate: the audit must exist, follow the
    schema, census real state, cover the wire vocabulary with
    verdicts, and carry zero unjustified seam hazards."""
    from ceph_tpu.analysis import seam_report
    _, project = analysis.run(TREE_PATHS, REPO)
    report = seam_report.build_report(project)
    assert report["schema"] == "ceph-tpu-seam-audit-v1"
    assert set(report) >= {"version", "shared_state",
                           "daemon_reaches", "wire_types",
                           "snapshot_races", "summary"}
    s = report["summary"]
    assert s["shared_state_sites"] >= 10
    assert s["wire_types"] >= 30
    assert s["unsafe_wire_types"] == []
    assert s["unhandled_wire_types"] == []
    assert s["unjustified_daemon_reaches"] == 0
    assert s["unjustified_snapshot_races"] == 0
    classes = {"fork-safe-cache", "per-process-counter",
               "per-process-primitive", "correctness-state"}
    for e in report["shared_state"]:
        assert e["classification"] in classes
        assert "analysis/" not in e["path"]
    for e in report["wire_types"]:
        assert e["verdict"] in ("wire-safe", "unsafe")
        assert e["codec"] in ("typed", "generic", "control",
                              "dynamic")
    # a justified entry must carry its why text
    for r in report["snapshot_races"] + report["daemon_reaches"]:
        assert r["justified"] and r["justification"]


def test_cli_seam_report(tmp_path):
    import json
    out = str(tmp_path / "audit.json")
    res = _cli("--seam-report", out)
    assert res.returncode == 0, res.stdout + res.stderr
    with open(out) as fh:
        doc = json.load(fh)
    assert doc["schema"] == "ceph-tpu-seam-audit-v1"
    assert doc["summary"]["shared_state_sites"] >= 10
