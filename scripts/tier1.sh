#!/usr/bin/env bash
# Tier-1 verify — static analysis gate, then the tests as the driver runs
# them after a PR (the command of /root/TESTS_LAST_RUN.json to the letter:
# six xdist workers that take the next test as they come free, --dist load,
# ALLOW_MULTIPLE_LIBTPU_LOAD=1, 1,470 s). CI and local runs use this wrapper
# so "what the driver checks" and "what you ran" cannot drift.

# named step: domain lint (guarded-by, host-sync-in-hot-path,
# donation-safety, jit-recompile-hazard, metrics-doc). Exit 1 here means a
# machine-checked invariant broke — fix it or lint-allow it with a reason.
echo "== analysis: python -m vnsum_tpu.analysis vnsum_tpu/ scripts/ =="
python -m vnsum_tpu.analysis vnsum_tpu/ scripts/ || exit 1

# named step: the tier-1 fast suite (the driver's command)
set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist load --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $rc
