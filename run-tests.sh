#!/usr/bin/env bash
# Test runner — the reference's `python/run-tests.sh`† analog (SURVEY.md §2
# "CI" row).  The reference script exported SPARK_HOME and the assembly jar
# onto the classpath before running nose; here the equivalent environment is
# the virtual 8-device CPU mesh (conftest.py re-asserts these, so running
# bare pytest also works — this script is the pinned entry point).
#
# Usage:
#   ./run-tests.sh              # full suite
#   ./run-tests.sh -m 'not slow'  # skip multi-process tests
#   ./run-tests.sh tests/test_sql.py  # one file
set -euo pipefail
cd "$(dirname "$0")"

export KERAS_BACKEND=jax
export JAX_PLATFORMS=cpu
if [[ "${XLA_FLAGS:-}" != *xla_force_host_platform_device_count* ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
fi

log=$(mktemp)
set +e
python -m pytest tests/ -q -rs "$@" 2>&1 | tee "$log"
rc=${PIPESTATUS[0]}
set -e
if [[ $rc -ne 0 ]]; then
  rm -f "$log"
  exit "$rc"
fi

# Honesty gate: a rig that ships every optional
# dependency (torch/transformers/keras/tensorflow/orbax, a C++ toolchain
# for the native targets) must report ZERO skipped tests — the suite's
# 241-passed-0-skipped signal is real; if oracle tests start silently
# skipping (a dep import regression, a guard typo), fail loudly instead
# of shrinking coverage.  Environment-INVERSE skips (tests that only run
# when a local imagenet cache is absent) are allowlisted; set
# SPARKDL_ALLOW_SKIPS=1 to disable the gate on partial rigs.
if [[ "${SPARKDL_ALLOW_SKIPS:-}" != "1" ]] && python -c '
import importlib.util as u, shutil, sys
deps = ("torch", "transformers", "keras", "tensorflow", "orbax.checkpoint")
ok = all(u.find_spec(m) for m in deps) and shutil.which("g++")
sys.exit(0 if ok else 1)
'; then
  if grep -E '^SKIPPED' "$log" | grep -vq 'imagenet cache exists'; then
    echo "run-tests: SKIPPED TESTS on a rig with all optional deps:" >&2
    grep -E '^SKIPPED|[0-9]+ skipped' "$log" | tail -20 >&2
    rm -f "$log"
    exit 1
  fi
fi
rm -f "$log"
