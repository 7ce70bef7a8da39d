#!/usr/bin/env bash
# Demo: a full adversarial spec debate on the mock engine (no TPU, no
# downloads), then the synthetic-TPU path. Run from the repo root.
#
#   examples/demo.sh                 # everything (tpu:// leg compiles XLA:
#                                    # ~1-3 min cold on a CPU box)
#   examples/demo.sh --skip-tpu-leg  # mock-only, finishes in seconds
set -euo pipefail
# Runs on the CPU unless JAX_PLATFORMS says otherwise (JAX_PLATFORMS=tpu
# puts the tpu:// leg on the chip; one process owns a chip at a time).
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}

RUN_TPU_LEG=1
if [[ "${1:-}" == "--skip-tpu-leg" ]]; then
  RUN_TPU_LEG=0
fi

SPEC='# Webhook Delivery Service

Delivers webhooks to customer endpoints with retries.

## Scope
v1 targets at-least-once delivery with exponential backoff.'

echo "=== Round 1: 3 opponents (one flaky), session tracked ==="
echo "$SPEC" | python3 -m adversarial_spec_tpu.cli critique \
  --models "mock://agree,mock://critic?agree_after=3,mock://flaky?fail=1&agree_after=2" \
  --doc-type tech --session demo --show-cost

for round in 2 3; do
  echo; echo "=== Round $round (resumed) ==="
  python3 -m adversarial_spec_tpu.cli critique --resume demo
done

echo; echo "=== Export the converged spec as tasks ==="
echo "$SPEC" | python3 -m adversarial_spec_tpu.cli export-tasks --models mock://tasks

if [[ "$RUN_TPU_LEG" == "1" ]]; then
  echo; echo "=== Synthetic tpu:// opponent (random weights, real engine) ==="
  echo "$SPEC" | python3 -m adversarial_spec_tpu.cli critique \
    --models tpu://random-tiny --greedy --max-new-tokens 32 2>/dev/null
else
  echo; echo "=== Synthetic tpu:// opponent: skipped (--skip-tpu-leg) ==="
fi

echo; echo "=== Cleanup ==="
rm -f .adversarial-spec-checkpoints/demo-round-*.md
python3 - <<'PY'
from adversarial_spec_tpu.debate.session import SESSIONS_DIR
p = SESSIONS_DIR / "demo.json"
p.unlink(missing_ok=True)
print("removed", p)
PY
