#!/usr/bin/env bash
# All pre-round gates in one command (CPU-only; no TPU needed).
#
#   bash tools/preflight.sh          # fast gate + contracts (~8 min)
#   bash tools/preflight.sh --full   # same gates, pytest incl. slow tier
#
# Gates: (1) pytest (fast tier by default; --full adds the slow tier),
# (2) entry() compile-check, (3) dryrun_multichip on 8 virtual devices.
set -euo pipefail
cd "$(dirname "$0")/.."

MARK=(-m "not slow")
[ "${1:-}" = "--full" ] && MARK=()

echo "== [1/3] pytest gate"
JAX_PLATFORMS=cpu python -m pytest tests/ -x -q "${MARK[@]}" -p no:cacheprovider

echo "== [2/3] entry() compile check"
JAX_PLATFORMS=cpu python - <<'EOF'
import jax
import __graft_entry__ as g
fn, args = g.entry()
jax.jit(fn)(*args)
print("entry OK")
EOF

echo "== [3/3] multichip dryrun (8 virtual devices)"
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
python - <<'EOF'
import __graft_entry__ as g
g.dryrun_multichip(8)
EOF

echo "== preflight PASSED"
