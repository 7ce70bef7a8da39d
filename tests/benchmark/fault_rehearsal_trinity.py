"""A run of a cell of the `afmoe` family with its layer's mathematics broken
underneath (not a test file). As a process of its own, `PERFBENCH_FAULT`
naming the fault, it is the rehearsal on the CPU, as `fault_rehearsal.py` is
for the three faults every configuration shares; with `PERFBENCH_ON_CHIP=1`
it is the command itself (`perfbench/run.py`'s `main`), which is how each
fault was read at the cell's size on the chip (`limits/trinity-large-int8-
ep8.json`). The daemon streams, counts and finishes as ever; only the
comparison with the reference can tell. `plant(fault)` alone breaks the
program in the caller's process (tests/test_afmoe.py compares logits).

- `window_sees_all`: a windowed layer attends to every earlier position
  (the window is as long as any context).
- `full_rotates`: a global layer rotates its queries and keys, as a
  windowed one does.
- `no_gate`: the heads' output reaches `W_o` without its sigmoid gate.
- `no_bias`: the router chooses by its scores alone, the experts' bias
  left out of the choice.
- `drop_expert`: the first held expert's part of every routed layer's
  result is left out (its pairs are treated as an absent expert's).
"""

import os
import sys
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

FAULTS = ("window_sees_all", "full_rotates", "no_gate", "no_bias", "drop_expert")


def plant(fault: str, patch=setattr) -> None:
    """Break the program in this process. `patch(object, name, value)`:
    `setattr`, or a test's `monkeypatch.setattr`, which undoes it."""
    from adversarial_spec_tpu.models import config, moe, transformer

    if fault == "window_sees_all":
        presets = {
            key: replace(cfg, gated=replace(cfg.gated, window=1 << 30))
            for key, cfg in config.CONFIGS.items()
            if cfg.gated is not None
        }
        patch(config, "CONFIGS", {**config.CONFIGS, **presets})
    elif fault == "full_rotates":
        sound = transformer._project_qkv

        def rotated(*args, kind="gqa", **kwargs):
            return sound(*args, kind="swa" if kind == "nope" else kind, **kwargs)

        patch(transformer, "_project_qkv", rotated)
    elif fault == "no_gate":
        patch(transformer, "_attn_gate", lambda lp, cfg, h, out, mm: out)
    elif fault == "no_bias":
        sound_route = moe.route

        def unbiased(h2, w_router, ex, bias=None):
            return sound_route(h2, w_router, ex, None if bias is None else bias * 0)

        patch(moe, "route", unbiased)
    elif fault == "drop_expert":
        import jax.numpy as jnp

        sound_group = moe.group_pairs

        def without_first(idx, ex, bm):
            return sound_group(jnp.where(idx == ex.first_held, -1, idx), ex, bm)

        patch(moe, "group_pairs", without_first)
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(os.environ["PERFBENCH_FAULT"])
    if os.environ.get("PERFBENCH_ON_CHIP") == "1":
        from perfbench import run as entry
    else:
        from perfbench import rehearse as entry
    code = entry.main(sys.argv[1:])
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
