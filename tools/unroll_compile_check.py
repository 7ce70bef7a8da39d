"""Compile-time cost of the decode-span layer-scan unroll at 70B depth.

``ADVSPEC_DECODE_UNROLL=4`` quadruples the
decode-scan body for an 80-layer config; is the compile-time cost
acceptable? This measures it directly: jit-compile one decode chunk for
an 80-layer (70B-depth) config at each unroll factor in a fresh
subprocess (the knob is read at transformer import) and print one JSON
line per setting. Dims are shrunk so the 80-layer compile fits CPU RAM
— XLA codegen scales with op count (layers / unroll bodies), which is
what the knob changes, so the RATIO is the signal even though absolute
times are CPU-backend numbers.

Usage: python tools/unroll_compile_check.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_CHILD = """
import json, os, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
# Fresh compile every time: a persistent-cache hit would hide exactly
# the cost being measured (and no cache directory is set here — the one
# site that places it is utils/jaxenv.py).
jax.config.update("jax_enable_compilation_cache", False)
import jax.numpy as jnp
from dataclasses import replace

from adversarial_spec_tpu.models import transformer as T
from adversarial_spec_tpu.models.config import get_config
from adversarial_spec_tpu.engine.generate import decode_chunk_steps

cfg = replace(get_config("llama", "tiny"), n_layers=80)  # 70B depth
params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
B, S, max_new = 1, 128, 128
cache = T.init_cache(cfg, B, S + max_new, dtype=jnp.float32)

t0 = time.monotonic()
out = decode_chunk_steps(
    params, cfg, cache,
    jnp.zeros((B,), jnp.int32),
    jnp.zeros((B,), jnp.int32),
    jnp.zeros((B,), bool),
    jnp.zeros((B, max_new), jnp.int32),
    jnp.int32(0), jnp.int32(8),
    jnp.asarray([-1], jnp.int32),
    jax.random.key(0), jnp.float32(0.7), jnp.float32(1.0),
    prompt_len=S, chunk=8, greedy=True, top_k=0, use_top_p=False,
    use_pallas_decode=False, pallas_interpret=False, mesh=None,
)
jax.block_until_ready(out[4])
wall = time.monotonic() - t0
print(json.dumps({
    "unroll": int(os.environ.get("ADVSPEC_DECODE_UNROLL", "4")),
    "n_layers": cfg.n_layers,
    "first_call_s": round(wall, 2),
}))
"""


def main() -> int:
    results = []
    for unroll in ("1", "2", "4"):
        env = dict(os.environ)
        env.update(ADVSPEC_DECODE_UNROLL=unroll, JAX_PLATFORMS="cpu")
        t0 = time.monotonic()
        out = subprocess.run(
            [sys.executable, "-c", _CHILD],
            env=env,
            capture_output=True,
            text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        if out.returncode != 0:
            print(out.stderr[-2000:], file=sys.stderr)
            return 1
        line = json.loads(out.stdout.strip().splitlines()[-1])
        line["proc_wall_s"] = round(time.monotonic() - t0, 2)
        results.append(line)
        print(json.dumps(line))
    base = results[0]["first_call_s"]
    for r in results[1:]:
        print(
            f"unroll={r['unroll']}: {r['first_call_s'] / base:.2f}x the "
            f"unroll=1 first-call (trace+compile) time at 80 layers"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
