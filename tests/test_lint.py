"""Tier-1 gate: fluidlint must run clean over the whole package.

Pure-AST analysis — no JAX tracing, CPU-only, fast.  A new finding
anywhere in ``fluidframework_tpu/`` fails this test; the only escape
hatch is a reviewed entry (with a non-empty ``reason``) in
``lint_baseline.json``, and stale/reason-less entries fail too, so the
baseline can only shrink through review.
"""

import pathlib

from tools.fluidlint import (all_rules, analyze, apply_baseline,
                             baseline_function_hygiene,
                             baseline_rule_hygiene, load_baseline)

ROOT = pathlib.Path(__file__).resolve().parents[1]
BASELINE = ROOT / "lint_baseline.json"


def test_package_lints_clean():
    """The one full three-family analysis pass of tier-1: every other
    lint test here runs against synthetic trees or in-memory sources, so
    the package-wide walk is paid exactly once per suite run."""
    findings = analyze(ROOT)
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    report = apply_baseline(findings, entries)
    problems = [f.render() for f in report.unsuppressed]
    problems += [f"baseline invalid: {m}" for m in report.invalid]
    problems += [
        f"baseline stale (matched no finding): [{e.get('rule')}] "
        f"{e.get('path')}: {e.get('message')}" for e in report.stale
    ]
    # Hygiene: suppression entries rot two ways — the function their
    # message names disappears, or the rule id itself is unregistered
    # (renamed/deleted rule).  Both fail the gate like a stale entry
    # (the finding they reviewed no longer describes live code).
    problems += [f"baseline hygiene: {m}"
                 for m in baseline_rule_hygiene(entries)
                 + baseline_function_hygiene(ROOT, entries)]
    assert not problems, (
        "fluidlint gate failed — fix the finding or add a REVIEWED "
        "suppression (with reason) to lint_baseline.json:\n"
        + "\n".join(problems))


def test_sharding_tier_modules_lint_clean_with_zero_suppressions():
    """ISSUE 7 acceptance pin: the two new serving modules pass ALL
    module rules (fluidlint + fluidrace + fluidleak families) with zero
    findings AND zero baseline entries — the package gate would let a
    reviewed suppression through; this test would not."""
    new_modules = [
        "fluidframework_tpu/service/sharding.py",
        "fluidframework_tpu/service/broadcaster.py",
    ]
    findings = analyze(ROOT, relpaths=new_modules)
    assert findings == [], [f.render() for f in findings]
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    offenders = [e for e in entries if e.get("path") in new_modules]
    assert offenders == [], "new modules must stay suppression-free"


def test_faultline_modules_lint_clean_with_zero_suppressions():
    """ISSUE 9 acceptance pin: the fault-injection engine and the retry
    policy pass ALL module rules (fluidlint + fluidrace + fluidleak
    families) with zero findings AND zero baseline entries — robustness
    machinery must hold itself to the discipline it enforces (bounded
    waits, no swallowed failures, no wall-clock on replay paths)."""
    new_modules = [
        "fluidframework_tpu/testing/faults.py",
        "fluidframework_tpu/service/retry.py",
    ]
    findings = analyze(ROOT, relpaths=new_modules)
    assert findings == [], [f.render() for f in findings]
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    offenders = [e for e in entries if e.get("path") in new_modules]
    assert offenders == [], "new modules must stay suppression-free"


def test_baseline_is_empty():
    """ISSUE 10 satellite pin: the last two FL-RACE-CHECKACT
    suppressions are BURNED — file_driver's probe-load-setdefault and
    catchup_cache's timeout reap are each restructured so every guarded
    touch is one critical section (probe/publish and reap helpers) — and
    the baseline is pinned at ZERO entries.  It can only stay empty:
    a new finding must be fixed, not reviewed in."""
    entries = load_baseline(BASELINE)
    assert entries == [], [e.get("path") for e in entries]


def test_fluidscale_modules_lint_clean_with_zero_suppressions():
    """ISSUE 10 acceptance pin: the swarm engine and the batched-ingress
    surfaces it drives pass ALL module rules (fluidlint + fluidrace +
    fluidleak families) with zero findings AND zero baseline entries —
    the scale harness must hold itself to the determinism and lifecycle
    discipline it measures."""
    new_modules = [
        "fluidframework_tpu/testing/scenarios.py",
        "fluidframework_tpu/protocol/sequencer.py",
        "fluidframework_tpu/service/oplog.py",
    ]
    findings = analyze(ROOT, relpaths=new_modules)
    assert findings == [], [f.render() for f in findings]
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    offenders = [e for e in entries if e.get("path") in new_modules]
    assert offenders == [], "new modules must stay suppression-free"


def test_fluidproc_modules_lint_clean_with_zero_suppressions():
    """ISSUE 12 acceptance pin: the out-of-process tier — shard host,
    front door (supervision, failover, live migration), and the proc
    client adapter — passes ALL module rules (fluidlint + fluidrace +
    fluidleak families) with zero findings AND zero baseline entries.
    Deployment machinery gets no exemptions: bounded waits, no wall
    clock on replay paths, every child process reaped or supervised."""
    new_modules = [
        "fluidframework_tpu/service/shardhost.py",
        "fluidframework_tpu/service/frontdoor.py",
        "fluidframework_tpu/service/procclient.py",
    ]
    findings = analyze(ROOT, relpaths=new_modules)
    assert findings == [], [f.render() for f in findings]
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    offenders = [e for e in entries if e.get("path") in new_modules]
    assert offenders == [], "new modules must stay suppression-free"


def test_device_cache_module_lints_clean_with_zero_suppressions():
    """ISSUE 13 acceptance pin: the device-resident pack-buffer tier
    passes ALL module rules (fluidlint + fluidrace + fluidleak families)
    with zero findings AND zero baseline entries — the module that
    donates device buffers must itself satisfy the donated-read
    discipline (FL-TRACE-DONATE) it motivated."""
    new_modules = [
        "fluidframework_tpu/ops/device_cache.py",
    ]
    findings = analyze(ROOT, relpaths=new_modules)
    assert findings == [], [f.render() for f in findings]
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    offenders = [e for e in entries if e.get("path") in new_modules]
    assert offenders == [], "new modules must stay suppression-free"


def test_kernel_family_modules_lint_clean_with_zero_suppressions():
    """ISSUE 14 acceptance pin: every module the family-generic pipeline
    refactor touched or created — the descriptor, both family bindings,
    the generic tiers, the mesh twin, the reason-counting router, and
    the second-family bench harness — passes ALL module rules (fluidlint
    + fluidrace + fluidleak families) with zero findings AND zero
    baseline entries.  The load-bearing generalization layer gets no
    exemptions."""
    new_modules = [
        "fluidframework_tpu/ops/family.py",
        "fluidframework_tpu/ops/pipeline.py",
        "fluidframework_tpu/ops/tree_pipeline.py",
        "fluidframework_tpu/ops/tree_kernel.py",
        "fluidframework_tpu/ops/batching.py",
        "fluidframework_tpu/ops/device_cache.py",
        "fluidframework_tpu/parallel/shard.py",
        "fluidframework_tpu/service/catchup.py",
        "tools/bench_kernels.py",
    ]
    findings = analyze(ROOT, relpaths=new_modules)
    assert findings == [], [f.render() for f in findings]
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    offenders = [e for e in entries if e.get("path") in new_modules]
    assert offenders == [], "new modules must stay suppression-free"


def test_fluiddur_modules_lint_clean_with_zero_suppressions():
    """ISSUE 17 acceptance pin: every module the durability family
    annotates — the oplog, the sequencer, both temp-write→publish
    drivers, the gate registry and its two consumers — passes ALL module
    rules (all four families) with zero findings AND zero baseline
    entries.  The crash-consistency contract is enforced, not reviewed
    around."""
    new_modules = [
        "fluidframework_tpu/service/oplog.py",
        "fluidframework_tpu/service/gates.py",
        "fluidframework_tpu/service/shardhost.py",
        "fluidframework_tpu/service/catchup.py",
        "fluidframework_tpu/service/server.py",
        "fluidframework_tpu/protocol/sequencer.py",
        "fluidframework_tpu/drivers/file_driver.py",
        "fluidframework_tpu/ops/native_pack.py",
    ]
    findings = analyze(ROOT, relpaths=new_modules)
    assert findings == [], [f.render() for f in findings]
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    offenders = [e for e in entries if e.get("path") in new_modules]
    assert offenders == [], "durability-annotated modules stay clean"


def test_fluidfail_modules_lint_clean_with_zero_suppressions():
    """ISSUE 19 acceptance pin: the error-taxonomy registry and every
    module the FL-ERR family audits — the five serving/driver modules
    that produce or consume wire error codes — pass ALL module rules
    with zero findings AND zero baseline entries.  The true positives
    the family caught (untyped broad handlers on reply paths, the
    ConnectionLostError retry hole) were FIXED, never baselined."""
    new_modules = [
        "fluidframework_tpu/protocol/errors.py",
        "fluidframework_tpu/drivers/network_driver.py",
        "fluidframework_tpu/service/server.py",
        "fluidframework_tpu/service/frontdoor.py",
        "fluidframework_tpu/service/shardhost.py",
        "fluidframework_tpu/service/procclient.py",
    ]
    findings = analyze(ROOT, relpaths=new_modules)
    assert findings == [], [f.render() for f in findings]
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    offenders = [e for e in entries if e.get("path") in new_modules]
    assert offenders == [], "error-taxonomy modules stay suppression-free"


def test_fluidshape_modules_lint_clean_with_zero_suppressions():
    """ISSUE 20 acceptance pin: every module the kernel family audits —
    both kernel families, the resident-buffer cache, the pipeline, and
    the mesh twin — passes ALL module rules (all six families) with zero
    findings AND zero baseline entries.  The true
    positives the family caught (unannotated narrow casts in the export
    path, the unroutable delta-fetch gather index) were annotated with
    reviewed reasons, never baselined."""
    new_modules = [
        "fluidframework_tpu/ops/mergetree_kernel.py",
        "fluidframework_tpu/ops/tree_kernel.py",
        "fluidframework_tpu/ops/device_cache.py",
        "fluidframework_tpu/ops/pipeline.py",
        "fluidframework_tpu/ops/family.py",
        "fluidframework_tpu/ops/interning.py",
        "fluidframework_tpu/parallel/shard.py",
    ]
    findings = analyze(ROOT, relpaths=new_modules)
    assert findings == [], [f.render() for f in findings]
    entries = load_baseline(BASELINE) if BASELINE.is_file() else []
    offenders = [e for e in entries if e.get("path") in new_modules]
    assert offenders == [], "kernel-layer modules stay suppression-free"


def test_counter_names_asserted_in_tests_are_produced():
    """ISSUE 17 satellite: counter-name drift.  Every namespaced counter
    literal a test references (catchup.*, fd.*, retry.*, swarm.*) must
    appear as a ``.bump()`` literal — or as the key of a
    ``span(name, acc, key)``, whose seconds are added to that counter —
    in the package: a renamed producer otherwise turns the assertion into
    a vacuous ``.get()`` default and the regression goes green.  A span's
    NAME is no counter: a test may name it only where it reads a trace,
    never as a ``.get()`` argument or a subscript."""
    import ast
    import re

    def literal(arg):
        return arg.value if isinstance(arg, ast.Constant) \
            and isinstance(arg.value, str) else None

    produced, span_names = set(), set()
    for path in (ROOT / "fluidframework_tpu").rglob("*.py"):
        tree = ast.parse(path.read_text(encoding="utf-8"))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            # direct counter bumps plus one-level bump-forwarding
            # helpers (the storm driver's `self._bump("swarm.storm_x")`
            # routes its literal to counters.bump)
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr.endswith("bump") and node.args
                    and literal(node.args[0]) is not None):
                produced.add(literal(node.args[0]))
            if isinstance(node.func, ast.Name) and node.func.id == "span":
                if node.args and literal(node.args[0]):
                    span_names.add(literal(node.args[0]))
                if len(node.args) > 2 and literal(node.args[2]):
                    produced.add(literal(node.args[2]))
    namespaces = {n.split(".", 1)[0] for n in produced if "." in n}
    assert namespaces, "no namespaced counters produced — check .bump() scan"
    assert "catchup.serve_s" in produced and "catchup.serve" in span_names
    # fault sites share the dotted-lowercase shape ('catchup.slow'); they
    # are owned by the seam registry, not the counter producers
    from fluidframework_tpu.testing import faults
    sites = set(faults.SITES)
    shape = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
    drifted = {}
    for path in sorted((ROOT / "tests").glob("*.py")):
        tree = ast.parse(path.read_text(encoding="utf-8"))
        reads = set()  # id()s of literals read as counters
        for node in ast.walk(tree):
            if isinstance(node, ast.Subscript):
                reads.add(id(node.slice))
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr == "get" and node.args):
                reads.add(id(node.args[0]))
        for node in ast.walk(tree):
            lit = literal(node)
            if lit is None:
                continue
            if (shape.match(lit) and lit.split(".", 1)[0] in namespaces
                    and lit not in sites and lit not in produced
                    and (lit not in span_names or id(node) in reads)):
                drifted.setdefault(lit, []).append(
                    f"{path.name}:{node.lineno}")
    assert not drifted, (
        f"tests reference counter names no package code bumps: {drifted}")


def test_every_rule_registered_and_described():
    rules = all_rules()
    # 9 (PR 2) + 6 fluidrace (PR 4) + 6 fluidleak (PR 5) + donate (PR 13)
    # + 6 fluiddur (PR 17) + 5 fluidfail (PR 19) + 5 fluidshape (PR 20)
    assert len(rules) >= 38, sorted(rules)
    for name, rule in rules.items():
        assert rule.description, f"{name} has no description"
        assert rule.severity in ("error", "warning"), name


def test_readme_catalog_covers_every_rule():
    """Docs cannot drift from the registry: the README rule tables must
    mention every registered rule id (pairs with --list-rules, which
    renders the same registry)."""
    text = (ROOT / "tools" / "fluidlint" / "README.md").read_text(
        encoding="utf-8")
    missing = [name for name in all_rules() if f"`{name}`" not in text]
    assert not missing, (
        f"tools/fluidlint/README.md does not document: {missing}")


def test_cli_list_rules_reports_family_and_severity(capsys):
    from tools.fluidlint.cli import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name, rule in all_rules().items():
        lines = [ln for ln in out.splitlines() if ln.startswith(name + " ")]
        assert len(lines) == 1, f"--list-rules missing {name}"
        assert f"/{rule.severity}]" in lines[0]
    assert "[lifecycle/error]" in out and "[concurrency/" in out


def test_cli_rules_family_filter(capsys):
    """ISSUE 17 satellite: `--rules dur` selects exactly the durability
    family (family name, not just rule-id prefix), and an unknown
    selector is a usage error, not a vacuously-clean run."""
    from tools.fluidlint.cli import main, rule_family

    assert main(["--rules", "dur", "--list-rules"]) == 0
    out = capsys.readouterr().out
    listed = {ln.split(" ", 1)[0] for ln in out.splitlines() if ln}
    expected = {name for name, rule in all_rules().items()
                if rule_family(rule) == "durability"}
    assert listed == expected and len(expected) == 6, (listed, expected)
    assert all("[durability/" in ln for ln in out.splitlines() if ln)
    assert main(["--rules", "nosuchfamily", "--list-rules"]) == 2
    capsys.readouterr()


def test_cli_rules_err_family_filter(capsys):
    """ISSUE 19: `--rules err` selects exactly the five-rule FL-ERR
    family (the error-taxonomy analyzer runs standalone)."""
    from tools.fluidlint.cli import main, rule_family

    assert main(["--rules", "err", "--list-rules"]) == 0
    out = capsys.readouterr().out
    listed = {ln.split(" ", 1)[0] for ln in out.splitlines() if ln}
    expected = {name for name, rule in all_rules().items()
                if rule_family(rule) == "errors"}
    assert listed == expected and len(expected) == 5, (listed, expected)
    assert all("[errors/" in ln for ln in out.splitlines() if ln)


def test_cli_rules_kern_family_filter(capsys):
    """ISSUE 20: `--rules kern` selects exactly the five-rule FL-KERN
    family (the kernel shape/dtype analyzer runs standalone — it is the
    first gate of tools/tpu_preflight.py)."""
    from tools.fluidlint.cli import main, rule_family

    assert main(["--rules", "kern", "--list-rules"]) == 0
    out = capsys.readouterr().out
    listed = {ln.split(" ", 1)[0] for ln in out.splitlines() if ln}
    expected = {name for name, rule in all_rules().items()
                if rule_family(rule) == "kernel"}
    assert listed == expected and len(expected) == 5, (listed, expected)
    assert all("[kernel/" in ln for ln in out.splitlines() if ln)


def test_cli_rules_family_filter_scopes_analysis(tmp_path, capsys):
    """A family-scoped run only reports that family's findings: a tree
    with one determinism violation is clean under `--rules dur`, red
    under `--rules det`."""
    from tools.fluidlint.cli import main

    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\ndef hold():\n    return time.time()\n")
    assert main(["--root", str(tmp_path), "--rules", "dur"]) == 0
    assert main(["--root", str(tmp_path), "--rules", "det"]) == 1
    capsys.readouterr()


def test_cli_exit_code_clean(tmp_path, capsys):
    # Pins the CLI wiring (exit 0 + summary line) against a tiny clean
    # tree: the package-wide walk is paid exactly once per suite run,
    # in test_package_lints_clean — never re-run here.
    from tools.fluidlint.cli import main

    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "ok.py").write_text("def fine():\n    return 1\n")
    assert main(["--root", str(tmp_path)]) == 0
    assert "0 error(s)" in capsys.readouterr().out


def test_module_entry_point_runs(tmp_path):
    """`python -m tools.fluidlint` is the documented gate command —
    __main__.py and the package import wiring need real subprocess
    coverage (over a one-file tree, so the package walk stays cheap)."""
    import subprocess
    import sys

    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\ndef hold():\n    return time.time()\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.fluidlint",
         "--root", str(tmp_path)],
        cwd=str(ROOT), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stderr
    assert "FL-DET-CLOCK" in proc.stdout


def test_cli_exit_code_on_findings(tmp_path, capsys):
    """The gate is real, not vacuous: a violation in a synthetic tree
    makes the CLI exit 1 and print the finding."""
    from tools.fluidlint.cli import main

    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\ndef hold():\n    return time.time()\n")
    assert main(["--root", str(tmp_path)]) == 1
    assert "FL-DET-CLOCK" in capsys.readouterr().out


def _seeded_git_tree(tmp_path):
    """A two-commit synthetic repo for --diff: ``stale.py`` carries a
    pre-existing finding and never changes after commit one;
    ``touched.py`` gains a finding in commit two; ``gone.py`` is deleted
    in commit two; ``fresh.py`` is untracked working-tree state."""
    import subprocess

    def git(*argv):
        subprocess.run(
            ["git", "-C", str(tmp_path), "-c", "user.email=t@test",
             "-c", "user.name=t", *argv],
            check=True, capture_output=True)

    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    bad = "import time\n\ndef hold():\n    return time.time()\n"
    (pkg / "stale.py").write_text(bad)
    (pkg / "touched.py").write_text("def fine():\n    return 1\n")
    (pkg / "gone.py").write_text("def bye():\n    return 2\n")
    git("init", "-q")
    git("add", "-A")
    git("commit", "-qm", "one")
    (pkg / "touched.py").write_text(bad)
    git("rm", "-q", str(pkg / "gone.py"))
    git("add", "-A")
    git("commit", "-qm", "two")
    (pkg / "fresh.py").write_text(bad)
    return pkg


def test_cli_diff_lints_only_changed_files(tmp_path, capsys):
    """ISSUE 19 satellite: `--diff GIT_REF` analyzes exactly the
    Python files changed since the ref (committed + working tree +
    untracked, deletions dropped) and reports the same findings a full
    run restricted to those files would — pre-existing findings in
    unchanged files stay out of the report."""
    import json

    from tools.fluidlint.cli import main

    _seeded_git_tree(tmp_path)
    assert main(["--root", str(tmp_path), "--diff", "HEAD~1",
                 "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert {f["path"] for f in report["unsuppressed"]} == {
        "fluidframework_tpu/loader/touched.py",
        "fluidframework_tpu/loader/fresh.py"}
    # identical findings contract: a full run restricted to the changed
    # files (the documented equivalence) produces the same report
    assert main(["--root", str(tmp_path),
                 "fluidframework_tpu/loader/touched.py",
                 "fluidframework_tpu/loader/fresh.py", "--json"]) == 1
    explicit = json.loads(capsys.readouterr().out)
    assert report["unsuppressed"] == explicit["unsuppressed"]
    # the unchanged file's finding exists — only a FULL run surfaces it
    assert main(["--root", str(tmp_path), "--json"]) == 1
    full = json.loads(capsys.readouterr().out)
    assert "fluidframework_tpu/loader/stale.py" in {
        f["path"] for f in full["unsuppressed"]}


def test_cli_diff_usage_and_git_errors(tmp_path, capsys):
    """--diff composes with nothing that contradicts it: explicit paths
    alongside it, an unknown ref, or a root outside any git repo are
    usage errors (exit 2), never a vacuously-clean exit 0."""
    from tools.fluidlint.cli import main

    repo = tmp_path / "repo"
    repo.mkdir()
    _seeded_git_tree(repo)
    assert main(["--root", str(repo), "--diff", "HEAD",
                 "fluidframework_tpu/loader/touched.py"]) == 2
    assert main(["--root", str(repo), "--diff", "no-such-ref"]) == 2
    # a root outside ANY git repo (sibling of the seeded one, so git
    # discovery cannot walk up into it)
    bare = tmp_path / "not-a-repo"
    (bare / "fluidframework_tpu").mkdir(parents=True)
    assert main(["--root", str(bare), "--diff", "HEAD"]) == 2
    capsys.readouterr()


def test_cli_sarif_writes_valid_report(tmp_path, capsys):
    """ISSUE 20 satellite: `--sarif FILE` writes a SARIF 2.1.0 document
    — registry as the tool driver, findings as results with
    repo-relative locations — while the text output and the exit code
    stay exactly what they were without it."""
    import json

    from tools.fluidlint.cli import main

    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\ndef hold():\n    return time.time()\n")
    sarif = tmp_path / "out.sarif"
    assert main(["--root", str(tmp_path), "--sarif", str(sarif)]) == 1
    assert "FL-DET-CLOCK" in capsys.readouterr().out
    doc = json.loads(sarif.read_text())
    assert doc["version"] == "2.1.0" and "2.1.0" in doc["$schema"]
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "fluidlint"
    ids = {r["id"] for r in driver["rules"]}
    assert "FL-DET-CLOCK" in ids and "FL-KERN-BLOCK" in ids
    assert all(r["shortDescription"]["text"] for r in driver["rules"])
    (hit,) = run["results"]
    assert hit["ruleId"] == "FL-DET-CLOCK" and hit["level"] == "error"
    loc = hit["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == \
        "fluidframework_tpu/loader/bad.py"
    assert loc["region"]["startLine"] >= 1
    assert "suppressions" not in hit


def test_cli_sarif_maps_reviewed_suppressions(tmp_path, capsys):
    """A baselined finding still appears in the SARIF output, carrying
    an ``external`` suppression whose justification is the reviewed
    reason — CI diff annotation sees WHAT was reviewed away and why."""
    import json

    from tools.fluidlint.cli import main

    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\ndef hold():\n    return time.time()\n")
    bp = tmp_path / "lint_baseline.json"
    assert main(["--root", str(tmp_path),
                 "--write-baseline", str(bp)]) == 0
    doc = json.loads(bp.read_text())
    for e in doc["suppressions"]:
        e["reason"] = "reviewed: synthetic fixture"
    bp.write_text(json.dumps(doc))
    capsys.readouterr()
    sarif = tmp_path / "out.sarif"
    assert main(["--root", str(tmp_path), "--baseline", str(bp),
                 "--sarif", str(sarif)]) == 0
    capsys.readouterr()
    run = json.loads(sarif.read_text())["runs"][0]
    (hit,) = run["results"]
    assert hit["ruleId"] == "FL-DET-CLOCK"
    (sup,) = hit["suppressions"]
    assert sup["kind"] == "external"
    assert sup["justification"] == "reviewed: synthetic fixture"


def test_cli_write_baseline_bootstraps_missing_file(tmp_path, capsys):
    """`--baseline X --write-baseline X` with no X yet is the bootstrap
    flow: it must write the skeleton, not die on 'baseline not found'
    (--write-baseline never reads the baseline)."""
    import json

    from tools.fluidlint.cli import main

    pkg = tmp_path / "fluidframework_tpu" / "loader"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "import time\n\ndef hold():\n    return time.time()\n")
    out = tmp_path / "lint_baseline.json"
    assert main(["--root", str(tmp_path), "--baseline", str(out),
                 "--write-baseline", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert len(doc["suppressions"]) == 1
    # a path that IS read (analysis / --check-baseline) still errors
    missing = str(tmp_path / "nope.json")
    assert main(["--root", str(tmp_path), "--baseline", missing]) == 2
    assert main(["--root", str(tmp_path), "--baseline", missing,
                 "--check-baseline"]) == 2
    capsys.readouterr()
