"""Tier-1 gate: the package lints clean under graftlint.

This is the CI teeth of the analysis/ subsystem — from this PR on, a
stray host sync in a jit region, an unguarded shared attribute in
serving/, or a missing donate_argnums on a step entry point fails the
quick tier (CPU-only, no jax import in the linter, sub-second), instead
of surfacing as a mysterious perf regression three PRs later.

Runs the CLI as a subprocess — exactly the documented invocation
(``python tools/graftlint.py differential_transformer_replication_tpu/``)
— so the gate also covers the wrapper and the --json plumbing."""

import json
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "differential_transformer_replication_tpu"
GRAFTLINT = REPO / "tools" / "graftlint.py"


def _lint_json():
    r = subprocess.run(
        [sys.executable, str(GRAFTLINT), "--json", str(PKG)],
        capture_output=True, text=True, cwd=str(REPO),
    )
    return r, (json.loads(r.stdout) if r.stdout else None)


def test_package_lints_clean():
    t0 = time.monotonic()
    r, doc = _lint_json()
    elapsed = time.monotonic() - t0
    # the full-tree gate must stay cheap enough for pre-commit: the
    # PR-11 interprocedural passes run in ~2s here; 15s is the ceiling
    # before the gate stops being run reflexively
    assert elapsed < 15.0, f"full-tree lint took {elapsed:.1f}s"
    assert doc is not None, f"no JSON output (stderr: {r.stderr})"
    active = [f for f in doc["findings"] if not f["suppressed"]]
    assert r.returncode == 0 and not active, (
        "graftlint found unsuppressed hazards (fix them or annotate the "
        "deliberate ones — see ANALYSIS.md):\n"
        + "\n".join(
            f"  {f['path']}:{f['line']}: {f['rule']} {f['message']}"
            for f in active
        )
        + f"\nparse errors: {doc['parse_errors']}"
    )
    assert doc["parse_errors"] == []


def test_engine_actually_analyzed_the_tree():
    """Guards the gate against vacuous passes: a regression that stops
    jit-region discovery (or file walking) would make every rule
    silently inapplicable while still exiting 0."""
    _, doc = _lint_json()
    assert doc["files_scanned"] >= 60, doc["files_scanned"]
    # train/step.py + engine closures + models stack + the PR-11
    # interprocedural expansion (Pallas kernels, shard_map bodies,
    # defvjp pairs) exceed this by a lot;
    # the floor pins that the expansion never silently regresses
    assert doc["jit_regions"] >= 200, doc["jit_regions"]
    # GL1xx-GL6xx: 10 original + 9 sharding/pallas/concurrency rules
    assert len(doc["rules"]) >= 13
    # the tree's deliberate exceptions stay visible as suppressed
    # findings — if this drops to zero the suppression plumbing broke
    # (or someone deleted the annotations wholesale; either needs eyes)
    assert doc["summary"]["suppressed"] >= 1


def test_fleet_tool_lints_clean():
    """GL6xx's second motivating surface (ISSUE: serving/ AND
    tools/fleet.py): the fleet supervisor's lock discipline is gated
    alongside the package."""
    r = subprocess.run(
        [sys.executable, str(GRAFTLINT), "--json",
         str(REPO / "tools" / "fleet.py")],
        capture_output=True, text=True, cwd=str(REPO),
    )
    doc = json.loads(r.stdout)
    active = [f for f in doc["findings"] if not f["suppressed"]]
    assert r.returncode == 0 and not active, active


def test_new_rule_families_fire_on_fixtures():
    """Anti-vacuity for the PR-11 families: the committed fixture files
    under tests/test_analysis/fixtures/ carry one planted hazard per
    rule — a pass that stops firing there is dead, and the clean-tree
    gate above would be meaningless."""
    r = subprocess.run(
        [sys.executable, str(GRAFTLINT), "--json",
         str(REPO / "tests" / "test_analysis" / "fixtures")],
        capture_output=True, text=True, cwd=str(REPO),
    )
    doc = json.loads(r.stdout)
    assert r.returncode == 1, "planted fixtures must fail the gate"
    active_rules = {
        f["rule"] for f in doc["findings"] if not f["suppressed"]
    }
    for rule in ("GL401", "GL402", "GL403", "GL501", "GL502", "GL503",
                 "GL504", "GL601", "GL602"):
        assert rule in active_rules, f"{rule} did not fire on its fixture"
    # every family also demonstrates auditable suppression plumbing
    assert any(f["suppressed"] for f in doc["findings"])
    # and the warn-level rule stays warn-level
    sev = {f["rule"]: f["severity"] for f in doc["findings"]}
    assert sev["GL503"] == "warning"


def test_lint_is_fast_enough_for_tier1():
    """The gate must stay cheap: stdlib-only, no jax import. A
    graftlint that starts importing jax would cost seconds per run and
    eventually a TPU lock — keep it static."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; "
         "import differential_transformer_replication_tpu.analysis.cli; "
         "sys.exit(1 if 'jax' in sys.modules else 0)"],
        cwd=str(REPO),
    )
    assert r.returncode == 0, "analysis CLI must not import jax"
