"""Two-process jax.distributed smoke test (multi-host dry story).

``maybe_initialize_distributed`` must be a *path*,
not just a guard — the v5p-16 multi-host config should not be first
exercised on scarce hardware. This launches two real OS processes that
each call maybe_initialize_distributed() via the documented env-var
contract, build the framework's {dp,tp,sp} mesh over the GLOBAL device
set, and run a cross-process psum. Runs on CPU (2 virtual devices per
process → 4 global), so it exercises process bring-up, the coordinator
handshake, and a DCN-analog collective with zero TPUs.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent

_PROBE = """
import jax
jax.config.update("jax_platforms", "cpu")
from adversarial_spec_tpu.parallel.mesh import (
    DP,
    make_mesh,
    maybe_initialize_distributed,
)
maybe_initialize_distributed()
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

n = jax.device_count()
assert n == 4, f"expected 4 global devices, got {n}"
assert jax.process_count() == 2
mesh = make_mesh({})  # all devices on dp, spanning both processes
x = jnp.arange(n, dtype=jnp.float32)
out = jax.shard_map(
    lambda v: jax.lax.psum(v, DP), mesh=mesh, in_specs=P(DP), out_specs=P()
)(x)
assert float(out[0]) == sum(range(n)), float(out[0])
print(f"OK proc={jax.process_index()} psum={float(out[0])}")
"""


_SPEC_PARITY = """
import jax
jax.config.update("jax_platforms", "cpu")
from adversarial_spec_tpu.parallel.mesh import (
    make_mesh,
    maybe_initialize_distributed,
)
maybe_initialize_distributed()
import jax.numpy as jnp
import numpy as np
from adversarial_spec_tpu.engine.generate import generate
from adversarial_spec_tpu.models import transformer as T
from adversarial_spec_tpu.models.config import get_config
from adversarial_spec_tpu.parallel.sharding import shard_params

assert jax.process_count() == 2 and jax.device_count() == 4
from adversarial_spec_tpu.engine.speculative import GAMMA

cfg = get_config("llama", "tiny")
params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
prompts = [[5 + i, 7, 11 + i, 13] for i in range(4)]
# Derived from GAMMA so an ADVSPEC_GAMMA override can't gate spec off.
kw = dict(max_new_tokens=2 * GAMMA + 8, eos_ids=[], greedy=True)

# Single-device reference (plain chunked decode, no mesh, no spec).
ref = generate(params, cfg, prompts, speculative=False, **kw)

# Cross-process dp=4 mesh with speculation ON: the host-side control
# flow must only fetch replicated scalars — any np.asarray of a
# dp-sharded array raises on non-addressable shards here.
mesh = make_mesh({})
sharded = shard_params(mesh, params)
out = generate(sharded, cfg, prompts, mesh=mesh, speculative=True, **kw)

np.testing.assert_array_equal(ref.tokens, out.tokens)
assert (ref.n_generated == out.n_generated).all()
print(f"OK proc={jax.process_index()} spec-parity")
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_two_process(probe_text, tmp_path, ok_marker, timeout=240):
    probe = tmp_path / "probe.py"
    probe.write_text(probe_text)
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        # Fresh interpreters WITHOUT the parent's jax state; PYTHONPATH
        # points at the repo only (drops any site customization that
        # would redirect jax at a hardware backend).
        env.update(
            PYTHONPATH=str(REPO_ROOT),
            JAX_PLATFORMS="cpu",
            JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2",
            JAX_PROCESS_ID=str(pid),
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(probe)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)  # CPU-only: safe to kill
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed smoke test timed out")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"OK proc={pid} {ok_marker}" in out, out


class TestLaunchContractErrors:
    """maybe_initialize_distributed fails fast, with the missing piece
    named, on a half-set launch contract — every branch raises BEFORE
    touching jax.distributed.initialize, so these run in-process."""

    def _call(self, monkeypatch, **env):
        from adversarial_spec_tpu.parallel.mesh import (
            maybe_initialize_distributed,
        )

        for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                  "JAX_PROCESS_ID"):
            monkeypatch.delenv(k, raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        maybe_initialize_distributed()

    def test_no_contract_is_noop(self, monkeypatch):
        self._call(monkeypatch)  # no env: plain single-process, no error

    def test_pieces_without_coordinator_fail(self, monkeypatch):
        with pytest.raises(RuntimeError, match="JAX_COORDINATOR_ADDRESS"):
            self._call(monkeypatch, JAX_NUM_PROCESSES="2")

    def test_coordinator_without_pid_fails(self, monkeypatch):
        with pytest.raises(RuntimeError, match="JAX_PROCESS_ID is not"):
            self._call(
                monkeypatch,
                JAX_COORDINATOR_ADDRESS="127.0.0.1:1",
                JAX_NUM_PROCESSES="2",
            )

    def test_coordinator_without_num_fails(self, monkeypatch):
        with pytest.raises(RuntimeError, match="JAX_NUM_PROCESSES is not"):
            self._call(
                monkeypatch,
                JAX_COORDINATOR_ADDRESS="127.0.0.1:1",
                JAX_PROCESS_ID="0",
            )

    def test_non_integer_contract_fails(self, monkeypatch):
        with pytest.raises(RuntimeError, match="must be integers"):
            self._call(
                monkeypatch,
                JAX_COORDINATOR_ADDRESS="127.0.0.1:1",
                JAX_NUM_PROCESSES="two",
                JAX_PROCESS_ID="0",
            )


@pytest.mark.slow
def test_two_process_distributed_psum(tmp_path):
    _run_two_process(_PROBE, tmp_path, "psum=6.0")


@pytest.mark.slow
def test_two_process_speculative_parity(tmp_path):
    """Speculative decode on a cross-process dp mesh matches the
    single-device greedy reference token-for-token (the host control
    flow must never fetch a non-addressable shard)."""
    _run_two_process(_SPEC_PARITY, tmp_path, "spec-parity", timeout=480)
