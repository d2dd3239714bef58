#!/usr/bin/env bash
# Standalone graftlint run over the enforced tree (titan_tpu/ +
# tests/): exit 0 clean, nonzero on unsuppressed findings. Extra
# args pass through (e.g. `scripts/lint.sh --json`, `--rules R1`,
# `--show-suppressed`). Rule catalog: docs/static-analysis.md.
set -euo pipefail
cd "$(dirname "$0")/.."
exec python -m tools.graftlint "$@"
