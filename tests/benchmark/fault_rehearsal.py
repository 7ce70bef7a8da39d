"""A rehearsal with the timed path broken underneath (not a test file: the
test starts it as a process of its own, `PERFBENCH_FAULT` naming the fault).
The daemon streams, counts and finishes as ever in both, and only the
comparison with the reference can tell.

- `alter_token`: every token the verify step produces is altered where it is
  produced (`accept_spans`' bonus token, shifted by one).
- `state_unchanged`: the step that hands a prefilled prompt's keys and values
  to the page pool returns the pool as it was (`write_tokens`), so the rows
  decode over a context that was never written.
- `rows_read_row_0`: every row of a verify step is given row 0's page table,
  so a row of another document reads (and writes) the first row's pages.
"""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from adversarial_spec_tpu.engine import scheduler  # noqa: E402

FAULT = os.environ["PERFBENCH_FAULT"]

if FAULT == "alter_token":
    _sound = scheduler.accept_spans

    def _altered(probs, *args, **kwargs):
        n_acc, bonus = _sound(probs, *args, **kwargs)
        return n_acc, (bonus + 1) % probs.shape[-1]

    scheduler.accept_spans = _altered
elif FAULT == "state_unchanged":
    scheduler.write_tokens = lambda pool, *args, **kwargs: pool
elif FAULT == "rows_read_row_0":
    import jax.numpy as jnp

    _sound = scheduler.ContinuousBatcher._dispatch_spec

    def _one_table(self, *args, **kwargs):
        table = self.page_table
        self.page_table = jnp.broadcast_to(table[:1], table.shape)
        try:
            return _sound(self, *args, **kwargs)
        finally:
            self.page_table = table

    scheduler.ContinuousBatcher._dispatch_spec = _one_table
else:
    raise SystemExit(f"unknown fault {FAULT!r}")

from perfbench import rehearse  # noqa: E402

if __name__ == "__main__":
    code = rehearse.main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(code)
