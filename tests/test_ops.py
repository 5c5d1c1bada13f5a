"""Ops-layer tests: gh_sync dry run, issue templates, CI workflow.

The reference's ops layer is gh_sync.ps1 + three issue forms (SURVEY.md
§2.1 #3-6). gh_sync.sh is the bash port; DRY_RUN=1 exercises its full
control flow — slug fallback, 27 labels, 11 issues — without the gh CLI.
"""

import os
import subprocess

import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_gh_sync_dry_run():
    out = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "gh_sync.sh")],
        env={**os.environ, "DRY_RUN": "1"},
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    label_posts = [l for l in lines if "repos/" in l and "/labels" in l]
    issue_creates = [l for l in lines if "issue create" in l]
    # 24+ labels (reference had 24; we add TPU-specific ones), 11 issues
    assert len(label_posts) >= 24, f"only {len(label_posts)} label ops"
    assert len(issue_creates) == 11, f"{len(issue_creates)} issues"
    assert "Done." in out.stdout
    # TPU retargeting: no GPU-flavored labels survive
    assert "area:tpu" in out.stdout
    assert "area:gpu" not in out.stdout


def _load(rel):
    with open(os.path.join(REPO, rel)) as f:
        return yaml.safe_load(f)


def test_issue_templates_valid():
    for name in ("task", "bug_report", "feature_request"):
        doc = _load(f".github/ISSUE_TEMPLATE/{name}.yml")
        assert doc["name"]
        assert isinstance(doc["body"], list) and doc["body"]
        ids = [b.get("id") for b in doc["body"] if b.get("id")]
        assert len(ids) == len(set(ids)), f"duplicate ids in {name}"


def test_task_template_requires_acceptance_criteria():
    """The acceptance-criteria requirement is the reference's
    verification-as-process mechanism (task.yml:12-21) — keep it required."""
    doc = _load(".github/ISSUE_TEMPLATE/task.yml")
    acc = next(b for b in doc["body"] if b.get("id") == "acceptance")
    assert acc["validations"]["required"] is True


def test_feature_template_area_labels():
    doc = _load(".github/ISSUE_TEMPLATE/feature_request.yml")
    area = next(b for b in doc["body"] if b.get("id") == "area")
    opts = area["attributes"]["options"]
    assert "area:tpu" in opts and "area:gpu" not in opts
    assert {"area:k8s", "area:data", "area:training", "area:monitoring",
            "area:ci", "area:docker"} <= set(opts)


def test_ci_workflow_valid():
    doc = _load(".github/workflows/ci.yml")
    # yaml parses the `on:` key as boolean True
    assert "jobs" in doc and ("on" in doc or True in doc)
    assert {"lint", "test"} <= set(doc["jobs"])
    steps = " ".join(str(s) for j in doc["jobs"].values()
                     for s in j.get("steps", []))
    assert "pytest" in steps and "shellcheck" in steps
