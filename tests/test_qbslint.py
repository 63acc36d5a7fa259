"""Fixture-based tests for tools/qbslint: every rule fires on its seeded
violation fixture, stays quiet on clean/suppressed code, and the CLI exit
codes match (0 = clean, 1 = findings)."""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.qbslint import ALL_RULES, lint_paths, lint_source  # noqa: E402

FIXTURES = REPO / "tests" / "fixtures" / "qbslint"


def _lint(path):
    findings, errors = lint_paths([path])
    assert not errors, errors
    return findings


def _rules(findings):
    return sorted({f.rule for f in findings})


def _cli(*argv, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.qbslint", *argv],
        cwd=cwd,
        capture_output=True,
        text=True,
    )


# ---------------------------------------------------------------- per-rule


def test_qbs001_catches_every_shard_map_route():
    findings = _lint(FIXTURES / "qbs001_bad.py")
    assert _rules(findings) == ["QBS001"]
    assert len(findings) == 4
    assert {f.line for f in findings} == {4, 5, 6, 11}


def test_qbs002_serving_scope_and_clock_exemption():
    findings = _lint(FIXTURES / "qbs002")
    assert _rules(findings) == ["QBS002"]
    assert len(findings) == 7
    by_file: dict = {}
    for f in findings:
        by_file.setdefault(f.path.rsplit("/", 1)[-1], []).append(f)
    assert set(by_file) == {"bad_wallclock.py", "bad_metrics.py"}
    assert len(by_file["bad_wallclock.py"]) == 5
    assert len(by_file["bad_metrics.py"]) == 2


def test_qbs003_host_sync_in_jit_bodies():
    findings = _lint(FIXTURES / "qbs003_bad.py")
    assert _rules(findings) == ["QBS003"]
    assert len(findings) == 6


def test_qbs004_jit_in_loop_and_per_call_body():
    findings = _lint(FIXTURES / "qbs004_bad.py")
    assert _rules(findings) == ["QBS004"]
    assert sorted(f.line for f in findings) == [8, 14]


def test_qbs005_unlocked_guarded_field_mutations():
    findings = _lint(FIXTURES / "qbs005_bad.py")
    assert _rules(findings) == ["QBS005"]
    assert sorted(f.line for f in findings) == [21, 22, 23, 24]


def test_qbs006_cache_insert_bypass():
    findings = _lint(FIXTURES / "qbs006_bad.py")
    assert _rules(findings) == ["QBS006"]
    assert sorted(f.line for f in findings) == [12, 13, 17]


def test_qbs007_host_widening_of_packed_tables():
    findings = _lint(FIXTURES / "qbs007_bad.py")
    assert _rules(findings) == ["QBS007"]
    assert sorted(f.line for f in findings) == [8, 9, 10, 11]


def test_qbs007_serving_int64_scope_and_suppression():
    findings = _lint(FIXTURES / "qbs007")
    assert _rules(findings) == ["QBS007"]
    assert sorted(f.line for f in findings) == [6, 10]
    assert all(f.path.endswith("bad_int64.py") for f in findings)


def test_qbs008_host_gather_of_sharded_tables():
    findings = _lint(FIXTURES / "qbs008")
    assert _rules(findings) == ["QBS008"]
    by_file = sorted((f.path.rsplit("/", 1)[-1], f.line) for f in findings)
    assert by_file == [("bad_gather.py", 7), ("bad_gather.py", 8),
                       ("bad_gather.py", 9), ("sharded.py", 6)]


def test_qbs008_host_boundary_marker_exempts_def():
    src = (
        "import numpy as np\n"
        "\n"
        "\n"
        "def save_shards(labels_sh):  # qbslint: host-boundary\n"
        "    return np.asarray(labels_sh)\n"
    )
    assert lint_source("serving/ckpt.py", src) == []
    # the same def without the marker fires
    assert _rules(lint_source("serving/ckpt.py",
                              src.replace("  # qbslint: host-boundary",
                                          ""))) == ["QBS008"]


def test_qbs009_table_mutation_outside_epoch_entry_points():
    findings = _lint(FIXTURES / "qbs009")
    assert _rules(findings) == ["QBS009"]
    # every finding sits in the bad fixture; the clean counterpart's
    # entry-point writes (__init__/apply_update/install_index/build*) and
    # its reasoned suppression stay silent
    assert all(f.path.endswith("bad_mutation.py") for f in findings)
    assert sorted(f.line for f in findings) == [10, 13, 14, 15, 16, 20]


def test_qbs009_subscript_into_unversioned_state_is_fine():
    src = (
        "class S:\n"
        "    def bump(self):\n"
        "        self.stats['updates'] = 1\n"
        "        self.flags.index = 3\n"
    )
    # writing *into* a non-table dict is fine; rebinding a '.index'
    # attribute is not, whatever the receiver
    assert _rules(lint_source("s.py", src)) == ["QBS009"]
    assert [f.line for f in lint_source("s.py", src)] == [4]


def test_qbs007_jit_bodies_are_exempt():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "\n"
        "@jax.jit\n"
        "def widen(label_dist, rows):\n"
        "    return label_dist[rows].astype(jnp.int32)\n"
    )
    assert lint_source("widen.py", src) == []


# ------------------------------------------------------------- negatives


def test_clean_fixture_has_no_findings():
    assert _lint(FIXTURES / "clean.py") == []


def test_suppressions_silence_findings():
    assert _lint(FIXTURES / "suppressed.py") == []


def test_line_suppression_is_rule_specific():
    src = "import jax\n\n\ndef caller(fn, x):\n    return jax.jit(fn)(x)  # qbslint: disable=QBS001\n"
    findings = lint_source("caller.py", src)
    assert _rules(findings) == ["QBS004"]


def test_bare_disable_silences_all_rules_on_line():
    src = "import jax\n\n\ndef caller(fn, x):\n    return jax.jit(fn)(x)  # qbslint: disable\n"
    assert lint_source("caller.py", src) == []


def test_repo_src_tree_is_clean():
    findings, errors = lint_paths([REPO / "src"])
    assert not errors, errors
    assert findings == [], "\n".join(f.render() for f in findings)


# ------------------------------------------------------------------- CLI


@pytest.mark.parametrize(
    "fixture",
    [
        "qbs001_bad.py",
        "qbs002",
        "qbs003_bad.py",
        "qbs004_bad.py",
        "qbs005_bad.py",
        "qbs006_bad.py",
        "qbs007_bad.py",
        "qbs007",
        "qbs008",
        "qbs009",
    ],
)
def test_cli_nonzero_on_each_seeded_violation(fixture):
    proc = _cli(str(FIXTURES / fixture))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "qbslint:" in proc.stdout


def test_cli_zero_on_repo_src():
    proc = _cli("src")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_rule_filter_and_json_output():
    proc = _cli(str(FIXTURES / "qbs005_bad.py"), "--rules", "QBS006", "--format", "json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []

    proc = _cli(str(FIXTURES / "qbs005_bad.py"), "--rules", "QBS005", "--format", "json")
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert {f["rule"] for f in payload["findings"]} == {"QBS005"}


def test_cli_list_rules_names_all_nine():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rule in ALL_RULES:
        assert rule.id in proc.stdout
    assert len(ALL_RULES) == 9
