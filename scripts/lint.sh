#!/usr/bin/env bash
# Repo lint gate: kolint (against the committed baseline), a compile
# sweep, and a check that no bytecode artifacts are tracked.
#
#   scripts/lint.sh            lint the package
#   scripts/lint.sh --json     machine-readable kolint output
#
# Exit nonzero on any finding not covered by kolint_baseline.json, any
# file that does not compile, or any tracked __pycache__/.pyc artifact.
set -u
cd "$(dirname "$0")/.."

rc=0

echo "== kolint =="
# --max-seconds keeps lint commit-loop fast; the .kolint_cache result
# cache this first pass warms makes the standalone passes below near-free
python -m kolibrie_tpu.analysis --max-seconds 60 "$@" kolibrie_tpu/ || rc=1

echo "== kolint cache-key versioning (KL901) =="
# the rule is in the default set above; this explicit pass keeps the
# cache-key discipline visible (and bisectable) on its own — result
# caches keyed on store identity must fold in (base_version,
# delta_epoch) or store.version_key() (docs/MQO.md)
python -m kolibrie_tpu.analysis --rules KL901 kolibrie_tpu/ || rc=1

echo "== kolint print hygiene (KL504) =="
# also in the default set; standalone pass keeps the no-bare-print
# discipline visible — library diagnostics go through obs/log.py, user
# output names its stream (docs/OBSERVABILITY.md)
python -m kolibrie_tpu.analysis --rules KL504 kolibrie_tpu/ || rc=1

echo "== kolint static races (KL311/KL312) =="
# the interprocedural race detector on its own: shared state written
# from >=2 thread roots must hold a lock at every access (docs/ANALYSIS.md)
python -m kolibrie_tpu.analysis --rules KL311,KL312 kolibrie_tpu/ || rc=1

echo "== kolint dataflow taint (KL111/KL112) =="
# def-use taint from traced params into host sinks and static/shape
# positions — the recompile-hazard class (docs/ANALYSIS.md)
python -m kolibrie_tpu.analysis --rules KL111,KL112 kolibrie_tpu/ || rc=1

echo "== lock sanitizer self-check =="
# the runtime cross-check of the static race rules: prove the
# KOLIBRIE_DEBUG_LOCKS instrumentation still catches a planted
# unguarded access before trusting its silence elsewhere
KOLIBRIE_DEBUG_LOCKS=1 python -c "
from kolibrie_tpu.analysis import lockcheck
assert lockcheck.selftest(), 'lockcheck.selftest() failed'
print('lockcheck selftest ok')
" || rc=1

echo "== compileall =="
# -q: names only on failure; PYTHONDONTWRITEBYTECODE keeps the tree clean
PYTHONDONTWRITEBYTECODE=1 python -m compileall -q kolibrie_tpu/ tests/ || rc=1

echo "== bytecode-free tree =="
tracked=$(git ls-files | grep -E '(__pycache__|\.pyc$)' || true)
if [ -n "$tracked" ]; then
    echo "tracked bytecode artifacts:" >&2
    echo "$tracked" >&2
    rc=1
fi
# Untracked __pycache__ dirs are build debris: a .pyc that outlives its
# deleted source keeps stale code importable by tooling that scans the
# tree. Catch them too — report and scrub so the gate leaves a clean tree.
strays=$(find kolibrie_tpu scripts tests -type d -name '__pycache__' 2>/dev/null || true)
if [ -n "$strays" ]; then
    echo "removing untracked bytecode dirs:"
    echo "$strays"
    echo "$strays" | xargs rm -rf
fi

exit $rc
