#!/usr/bin/env bash
# Tree hygiene gate (tier-1): no tracked bytecode, src compiles, and the
# user-facing docs exist with file references that resolve.
set -euo pipefail
cd "$(dirname "$0")/.."

bad=$(git ls-files | grep -E '(^|/)__pycache__/|\.pyc$' || true)
if [ -n "$bad" ]; then
    echo "ERROR: tracked bytecode files:" >&2
    echo "$bad" >&2
    exit 1
fi

python -m compileall -q src

# docs gate: first-class docs must exist ...
for doc in README.md docs/ARCHITECTURE.md; do
    if [ ! -f "$doc" ]; then
        echo "ERROR: missing $doc" >&2
        exit 1
    fi
done
# ... and every repo-relative file reference inside them must resolve
# (paths containing a directory separator, e.g. src/repro/core/engine.py,
# bench/run.py — bare names like ops.py are not checked, and URLs
# are stripped first so external links never trip the gate)
missing=0
for doc in README.md docs/ARCHITECTURE.md; do
    while IFS= read -r ref; do
        if [ ! -e "$ref" ]; then
            echo "ERROR: $doc references missing path: $ref" >&2
            missing=1
        fi
    done < <(sed -E 's#[a-z]+://[^ )>]*##g' "$doc" \
             | grep -oE '[A-Za-z0-9_.-]+(/[A-Za-z0-9_.-]+)+\.(py|sh|md|json)' \
             | sort -u)
done
if [ "$missing" -ne 0 ]; then
    exit 1
fi

# static-analysis gate: the four concurrency passes over the serving
# core (lock discipline, donation safety, protocol exhaustiveness,
# thread hygiene) plus the docs cross-check.  Only findings NOT in the
# committed baseline fail — introducing a new one breaks the build.
PYTHONPATH=src python -m repro.analysis.lint --baseline

echo "check_tree: OK"
