"""A rehearsal of a cell with state-space layers with the recurrent state
broken underneath (not a test file: the test starts it as a process of its
own, `PERFBENCH_FAULT` naming the fault, as `fault_rehearsal.py` does for
the three faults every configuration shares). The daemon streams, counts
and finishes as ever in both, and only the comparison with the reference
can tell.

- `keep_rejected_state`: a verify step commits every position of its span,
  whatever was accepted (`commit_span`'s count is replaced by the span's
  width for every live row), so the state holds the rejected drafts.
- `restore_no_snapshot`: an admission over cached pages starts its delta
  from an empty state row, as a sequence's first token does
  (`_write_state_row_impl` is handed zeros for the snapshot it resumes
  at), so the matched prefix is in the pages and not in the state.
"""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from adversarial_spec_tpu.engine import scheduler  # noqa: E402

FAULT = os.environ["PERFBENCH_FAULT"]

if FAULT == "keep_rejected_state":
    import jax.numpy as jnp

    _sound = scheduler.commit_span

    def _keep_all(cfg, pool, n_keep, **kwargs):
        span = pool["span"]["dt"].shape[2]
        return _sound(cfg, pool, jnp.where(n_keep > 0, span, 0), **kwargs)

    scheduler.commit_span = _keep_all
elif FAULT == "restore_no_snapshot":
    import jax

    _sound = scheduler._write_state_row_impl

    def _from_nothing(pool, slot, state):
        return _sound(pool, slot, jax.tree.map(lambda a: a * 0, state))

    scheduler._write_state_row_impl = _from_nothing
else:
    raise SystemExit(f"unknown fault {FAULT!r}")

from perfbench import rehearse  # noqa: E402

if __name__ == "__main__":
    code = rehearse.main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(code)
