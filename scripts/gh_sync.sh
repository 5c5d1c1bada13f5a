#!/usr/bin/env bash
# Idempotent GitHub project sync: label scheme + issue backlog.
#
# Bash port of the reference's scripts/gh_sync.ps1 (structure:
# Get-RepoSlug :5-15, Ensure-Label GET->PATCH/POST :17-35, Ensure-Issue
# search-by-title->edit/create :37-49, auth preflight :51-57, 24-label
# table :63-97, 11-issue table :103-159), retargeted to this TPU stack:
# area:gpu becomes area:tpu, the training labels name JAX/pjit instead of
# PyTorch/DDP, and the backlog tracks the TPU build's components.
#
# DRY_RUN=1 prints every action instead of calling gh — used by
# tests/test_ops.py and safe to run anywhere.
set -euo pipefail

DRY_RUN="${DRY_RUN:-0}"

run_gh() {
  if [[ "$DRY_RUN" == "1" ]]; then
    echo "DRY: gh $*"
  else
    gh "$@" >/dev/null
  fi
}

# --- preflight (gh present + authenticated; ps1:51-57) ----------------------
if [[ "$DRY_RUN" != "1" ]]; then
  command -v gh >/dev/null || { echo "gh CLI not installed" >&2; exit 1; }
  gh auth status >/dev/null || { echo "gh not authenticated" >&2; exit 1; }
fi

# --- repo slug from the origin remote (ps1:5-15) ----------------------------
repo_slug() {
  local url
  url="$(git remote get-url origin 2>/dev/null || true)"
  url="${url%.git}"
  if [[ "$url" =~ github\.com[:/]([^/]+/[^/]+)$ ]]; then
    echo "${BASH_REMATCH[1]}"
  else
    echo ""
  fi
}
REPO="${REPO:-$(repo_slug)}"
if [[ -z "$REPO" ]]; then
  echo "cannot derive repo slug from origin remote; set REPO=owner/name" >&2
  if [[ "$DRY_RUN" == "1" ]]; then
    REPO="example/tpu-disttrain"
  else
    exit 1
  fi
fi
echo "Using repo: $REPO"

# --- label scheme (24 labels; ps1:63-97 adapted to the TPU stack) ---------
# format: name|color|description
LABELS=(
  "type:bug|d73a4a|Something isn't working"
  "type:enhancement|a2eeef|New feature or improvement"
  "type:documentation|0075ca|Docs, README, or playbook work"
  "type:task|cfd3d7|Actionable task"
  "type:chore|d4c5f9|Build, tooling, maintenance"
  "area:k8s|0e8a16|Kubernetes manifests & cluster"
  "area:tpu|1f883d|TPU runtime, libtpu, device plugin, ICI"
  "area:docker|0366d6|Dockerfiles and images"
  "area:data|fbca04|Datasets and storage"
  "area:training|5319e7|JAX training core, pjit sharding, model config"
  "area:monitoring|a2eeef|Logs, metrics, TensorBoard, profiler"
  "area:ci|d876e3|CI/CD scripts and workflows"
  "priority:P0|b60205|Critical"
  "priority:P1|d93f0b|High"
  "priority:P2|fbca04|Medium"
  "priority:P3|e4e669|Low"
  "status:blocked|e11d21|Blocked on external dependency"
  "status:needs-info|c5def5|Needs clarification or data"
  "status:ready|0e8a16|Ready to pick up"
  "good first issue|7057ff|Good for newcomers"
  "help wanted|008672|Contributions welcome"
  "size:XS|ededed|< 30 min"
  "size:S|c5def5|~1-2 hours"
  "size:M|bfdadc|~1 day"
  "size:L|c2e0c6|> 1 day"
  "security|ee0701|Security implications"
  "question|d876e3|Further information requested"
)

ensure_label() {
  local name="$1" color="$2" desc="$3"
  if [[ "$DRY_RUN" != "1" ]] && gh api \
      "repos/${REPO}/labels/$(printf %s "$name" | sed 's/ /%20/g')" \
      >/dev/null 2>&1; then
    run_gh api -X PATCH "repos/${REPO}/labels/${name}" \
      -f new_name="$name" -f color="$color" -f description="$desc"
  else
    # Tolerate ONLY the already-exists race (two syncs colliding); any
    # other failure (auth scope, rate limit) must stop the script.
    if ! out="$(run_gh api -X POST "repos/${REPO}/labels" \
          -f name="$name" -f color="$color" -f description="$desc" 2>&1)"; then
      if [[ "$out" != *"already_exists"* ]]; then
        echo "$out" >&2
        exit 1
      fi
    elif [[ "$DRY_RUN" == "1" ]]; then
      echo "$out"
    fi
  fi
}

echo "Syncing labels..."
for row in "${LABELS[@]}"; do
  IFS='|' read -r name color desc <<<"$row"
  ensure_label "$name" "$color" "$desc"
done

# --- issue backlog (ps1:103-159 adapted; doubles as the component list) -----
ensure_issue() {
  local title="$1" body="$2" labels="$3"
  local existing=""
  if [[ "$DRY_RUN" != "1" ]]; then
    existing="$(gh issue list --repo "$REPO" --state all \
      --search "in:title \"$title\"" --json number,title \
      --jq ".[] | select(.title == \"$title\") | .number" | head -1)"
  fi
  if [[ -n "$existing" ]]; then
    run_gh issue edit "$existing" --repo "$REPO" --add-label "$labels"
  else
    run_gh issue create --repo "$REPO" --title "$title" --body "$body" \
      --label "$labels"
  fi
}

echo "Creating issues..."
ensure_issue "Configure corporate proxy for Pods and builds" \
  "Set HTTP_PROXY/HTTPS_PROXY/NO_PROXY in k8s/01-proxy-config.yaml and verify egress for dataset prep; keep the JAX coordinator rendezvous on NO_PROXY." \
  "type:task,area:k8s,priority:P0,status:ready,size:S"
ensure_issue "Provision TPU cluster (GKE node pool or kind for CI)" \
  "MODE=gke scripts/01_install_cluster.sh creates the TPU node pool; validate google.com/tpu is allocatable. MODE=kind for CPU-only manifest validation." \
  "type:task,area:k8s,area:tpu,priority:P0,status:ready,size:S"
ensure_issue "Build and load jax[tpu] training image" \
  "Use scripts/02_build_and_load_image.sh (TARGET=kind|k3s|push) to build docker/Dockerfile and make it pullable by the cluster." \
  "type:task,area:docker,priority:P1,status:ready,size:S"
ensure_issue "Create storage (hostPath single-node or Filestore RWX) and verify write perms" \
  "STORAGE=hostpath|filestore scripts/03_apply_basics.sh; ensure Pods can write /data." \
  "type:task,area:k8s,priority:P1,status:ready,size:S"
ensure_issue "Dataset job: tiny Shakespeare char-level" \
  "Run k8s/jobs/20-download-tiny-shakespeare.yaml to generate train/val bins at /data/datasets/shakespeare_char." \
  "type:task,area:data,priority:P1,status:ready,size:S"
ensure_issue "Single-Pod multi-chip training (v4-8 host)" \
  "Run k8s/jobs/30-train-singlepod.yaml requesting google.com/tpu: 4; pjit data-parallels over the local chips in one SPMD process." \
  "type:enhancement,area:training,area:tpu,priority:P1,status:ready,size:M"
ensure_issue "Validate multi-Pod multi-host StatefulSet" \
  "Headless Service + StatefulSet(4 replicas): jax.distributed.initialize rendezvous via pod-0 DNS, ordinal-derived process_id, end-to-end training." \
  "type:task,area:k8s,area:training,priority:P1,status:ready,size:M"
ensure_issue "TensorBoard: document workflow and logdir conventions" \
  "Document reading TensorBoard + jax.profiler logs from /data/runs and safe copying off-cluster without exposing a service." \
  "type:documentation,area:monitoring,priority:P2,status:ready,size:S"
ensure_issue "Add medium dataset Job (OpenWebText subset)" \
  "k8s/jobs/21-download-openwebtext.yaml streams an OWT subset, size via DATASET_NUM_CHARS env." \
  "type:enhancement,area:data,priority:P2,status:ready,size:M,good first issue"
ensure_issue "Document ICI/DCN collective mapping (replaces NCCL presets)" \
  "docs/collectives.md: how XLA places all-reduce on ICI within a slice and DCN across slices; what replaced NCCL_IB_DISABLE/SOCKET_IFNAME." \
  "type:documentation,area:training,area:tpu,priority:P2,status:ready,size:S"
ensure_issue "Add CI: lint YAML and shell scripts, run pytest tiers" \
  "GitHub Actions workflow: manifest/shell lint (tests/test_deploy.py) plus the JAX-CPU test tiers." \
  "type:chore,area:ci,priority:P3,status:ready,size:S,help wanted"

echo "Done."
