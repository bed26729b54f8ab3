#!/usr/bin/env bash
# graftlint gate: the package must be lint-clean, and the suppression
# inventory must match the committed baseline (scripts/lint_baseline.json) —
# a new `# lint: ...-ok` marker is a reviewable event, not ambient noise.
#
#   ./scripts/lint_gate.sh            # gate (exit 1 on violations or drift)
#   ./scripts/lint_gate.sh --update   # regenerate the baseline after review
#
# The baseline keys suppressions by (rule, path, reason, rule_version) —
# line-insensitive, so unrelated edits that shift code don't churn the
# gate, but keyed to the rule's implementation hash: editing a rule
# invalidates every suppression written against the old behaviour, so a
# changed check forces its silenced findings back into review.
set -euo pipefail
cd "$(dirname "$0")/.."

BASELINE=scripts/lint_baseline.json
CURRENT=$(mktemp)
trap 'rm -f "$CURRENT"' EXIT

# the CLI exits 1 when it finds violations; the diff below reports them
JAX_PLATFORMS=cpu \
  python -m deeplearning4j_tpu.lint deeplearning4j_tpu --json \
  > "$CURRENT" || true

MODE=gate
[ "${1-}" = "--update" ] && MODE=update

MODE=$MODE CURRENT=$CURRENT BASELINE=$BASELINE python - <<'EOF'
import json
import os
import sys

cur = json.load(open(os.environ["CURRENT"]))
versions = cur.get("rule_versions", {})


def sup_keys(report):
    return {(s["rule"], s["path"], s.get("reason", ""),
             versions.get(s["rule"], ""))
            for s in report.get("suppressed", [])}


if os.environ["MODE"] == "update":
    baseline = {
        "comment": "graftlint baseline — regenerate with "
                   "./scripts/lint_gate.sh --update after reviewing "
                   "suppression changes; rule_version pins the rule "
                   "implementation each suppression was reviewed against",
        "files_scanned": cur["files_scanned"],
        "suppressed": [
            {"rule": r, "path": p, "reason": why, "rule_version": ver}
            for r, p, why, ver in sorted(sup_keys(cur))],
    }
    with open(os.environ["BASELINE"], "w") as f:
        json.dump(baseline, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"baseline updated: {len(baseline['suppressed'])} suppression(s)")
    sys.exit(0)

failed = False
if cur["violations"] or cur["errors"]:
    failed = True
    for e in cur["errors"]:
        print(f"ERROR {e}")
    for v in cur["violations"]:
        print(f"{v['path']}:{v['line']}: [{v['rule']}] {v['message']}")

base = json.load(open(os.environ["BASELINE"]))
base_keys = {(s["rule"], s["path"], s["reason"],
              s.get("rule_version", ""))
             for s in base["suppressed"]}
cur_keys = sup_keys(cur)
stale = {k[0] for k in base_keys
         if k[3] and versions.get(k[0]) and k[3] != versions[k[0]]}
for rule in sorted(stale):
    failed = True
    print(f"rule '{rule}' implementation changed since the baseline was "
          "reviewed — its suppressions are stale; re-review them and "
          "./scripts/lint_gate.sh --update")
for key in sorted(cur_keys - base_keys):
    if key[0] in stale:
        continue  # already reported as a stale-rule re-review above
    failed = True
    print("new suppression not in baseline: "
          "[%s] %s (%s)" % key[:3])
for key in sorted(base_keys - cur_keys):
    if key[0] in stale:
        continue
    failed = True
    print("baseline suppression no longer present (run --update): "
          "[%s] %s (%s)" % key[:3])

if failed:
    print("lint gate FAILED — fix the findings or, for reviewed "
          "suppression changes, ./scripts/lint_gate.sh --update",
          file=sys.stderr)
    sys.exit(1)
print(f"lint gate ok: {cur['files_scanned']} files, "
      f"{len(cur_keys)} suppression(s) matching baseline")
EOF
