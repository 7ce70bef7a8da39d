"""The system under test, started inside the benchmark's process.

Only the process that holds the chip can trace it, so the `advspec serve`
daemon runs here, on a thread, through the entry a user calls
(`cli.main(["serve", "--socket", ...])`): every option of the program is at
its default. What a run writes (registry, sessions, socket, HOME) lives
under a directory of the run's own, emptied at the start, so that only the
compile cache outlasts a run.

Three things are read from the program besides its socket:

- its counters (`counters()`): the obs registry, the serve, speculation and
  prefix-cache blocks and the device report, flattened to `block.key`;
- a tap on `ContinuousBatcher.submit` / `run_all`, the batcher's public
  calls: the prompt ids and the served token ids of every request (the
  client sees text only, and a random 32k-vocabulary model's ids mostly do
  not print), the count of ids at each delivery, and how many different
  prompts each dispatch held. Nothing is switched:
  the calls are wrapped from out here to see their arguments. A request
  that does not pass the tap was not served by the batcher;
- the sizes of the parameter tree the engine holds, by top-level name.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path

from perfbench.manifest import ROOT

ALIAS = "bench-model"
RUN_DIR_NAME = ".perfbench_run"


class DaemonStartError(Exception):
    pass


def prepare_run_dir(root: Path = ROOT) -> Path:
    """An empty directory for this run, at a fixed path inside the checkout.

    Must be called before anything of the program is imported: the
    registry's path is taken from HOME when its module loads.
    """
    run_dir = Path(root) / RUN_DIR_NAME
    shutil.rmtree(run_dir, ignore_errors=True)
    (run_dir / "home").mkdir(parents=True)
    (run_dir / "sessions").mkdir()
    os.environ["HOME"] = str(run_dir / "home")
    os.environ["ADVSPEC_SESSIONS_DIR"] = str(run_dir / "sessions")
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    return run_dir


def write_registry(run_dir: Path, serving: dict) -> dict:
    """The cell's model as a registry entry, in the run's own registry file:
    the configuration's whole `serving` group laid over the defaults, so
    that every key it gives (a mesh, a share of a deployment) reaches the
    program. `family` and `size` have no default."""
    entry = {
        "alias": ALIAS,
        "family": serving["family"],
        "checkpoint": "random",
        "tokenizer": "",
        "size": serving["size"],
        "dtype": "bfloat16",
        "mesh": {"dp": 1, "tp": 1, "sp": 1},
        "max_seq_len": 0,
        "n_layers": 0,
        "quant": "",
        "kv": "paged",
        "kv_dtype": "",
        **serving,
    }
    path = run_dir / "home/.config/adversarial-spec-tpu/registry.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({ALIAS: entry}, indent=2))
    return entry


def devices() -> dict:
    """What jax runs on here, through the program's own configuration
    (platform from JAX_PLATFORMS, the one compile cache directory)."""
    from adversarial_spec_tpu.utils.jaxenv import configure_jax

    configure_jax()
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def dispatch_rows() -> int:
    """The most rows the daemon puts into one engine dispatch."""
    from adversarial_spec_tpu import serve as serve_mod

    return int(serve_mod.config().max_dispatch_batch)


def memory_peak_bytes() -> int:
    import jax

    peaks = [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()
    ]
    return max(peaks)


class Tap:
    """Prompt ids, served ids and delivery sizes of every batcher request,
    keyed by the request's span id (the result payload echoes it)."""

    def __init__(self) -> None:
        self.requests: dict[str, dict] = {}
        self._pending: dict[tuple, dict] = {}
        self._orig: tuple | None = None
        self._n = 0
        self.counts = {"submitted": 0, "run_all_calls": 0, "results": 0, "distinct_prompts": 0}

    def install(self) -> None:
        from adversarial_spec_tpu.engine.scheduler import ContinuousBatcher

        orig_submit, orig_run_all = ContinuousBatcher.submit, ContinuousBatcher.run_all
        self._orig = (ContinuousBatcher, orig_submit, orig_run_all)
        tap = self

        def submit(batcher, req):
            tap._n += 1
            tap.counts["submitted"] += 1
            rec = {
                "span_id": req.span_id or f"anon{tap._n}",
                "prompt_ids": [int(t) for t in req.prompt_ids],
                "deliveries": [],  # ids emitted so far, at each delivery
                "tokens": None,
                "error": None,
                "cancelled": False,
            }
            tap._pending[(id(batcher), req.req_id)] = rec
            inner = req.on_tokens
            if inner is not None:
                def on_tokens(token_ids, _inner=inner, _rec=rec):
                    _rec["deliveries"].append(len(token_ids))
                    return _inner(token_ids)

                req.on_tokens = on_tokens
            return orig_submit(batcher, req)

        def run_all(batcher, *args, **kwargs):
            tap.counts["run_all_calls"] += 1  # at entry, as `submitted` is
            tap.counts["distinct_prompts"] += len(
                {tuple(rec["prompt_ids"]) for (b, _), rec in tap._pending.items() if b == id(batcher)}
            )
            out = orig_run_all(batcher, *args, **kwargs)
            tap.counts["results"] += len(out)
            for r in out:
                rec = tap._pending.pop((id(batcher), r.req_id), None)
                if rec is None:
                    continue
                rec["tokens"] = [int(t) for t in r.tokens[: r.n_generated]]
                rec["error"] = r.error
                rec["cancelled"] = bool(r.cancelled)
                tap.requests[rec["span_id"]] = rec
            return out

        ContinuousBatcher.submit = submit
        ContinuousBatcher.run_all = run_all

    def uninstall(self) -> None:
        if self._orig is not None:
            cls, submit, run_all = self._orig
            cls.submit, cls.run_all = submit, run_all
            self._orig = None


def _flatten(prefix: str, node, out: dict) -> None:
    if isinstance(node, dict):
        for k, v in node.items():
            _flatten(f"{prefix}.{k}" if prefix else str(k), v, out)
    elif isinstance(node, bool):
        out[prefix] = int(node)
    elif isinstance(node, (int, float)):
        out[prefix] = node


def counters(tap: Tap | None = None) -> dict:
    """Every counter the program keeps, flat: `obs.<series>` (histograms as
    `.count` and `.sum`), `serve.*`, `spec.*`, `prefix.*`, `stream.*`,
    `device.*` (memory and compile counts), and the tap's own counts of the
    batcher's calls (`batcher.submitted`, `batcher.run_all_calls`,
    `batcher.distinct_prompts`)."""
    from adversarial_spec_tpu import obs as obs_mod
    from adversarial_spec_tpu import serve as serve_mod
    from adversarial_spec_tpu.engine import prefix_cache, spec, streaming
    from adversarial_spec_tpu.utils import jaxenv

    out: dict = {}
    _flatten("obs", obs_mod.metrics.snapshot(), out)
    _flatten("serve", serve_mod.snapshot(), out)
    _flatten("spec", spec.snapshot(), out)
    _flatten("prefix", prefix_cache.snapshot(), out)
    _flatten("stream", streaming.snapshot(), out)
    _flatten("device", jaxenv.device_report() or {}, out)
    if tap is not None:
        _flatten("batcher", tap.counts, out)
    return out


class System:
    """`advspec serve` on a thread of this process, on a socket of the run's own."""

    def __init__(self, run_dir: Path, serving: dict) -> None:
        self.run_dir = run_dir
        self.serving = serving
        self.model = f"tpu://{ALIAS}"
        self.tap = Tap()
        self._thread: threading.Thread | None = None
        self._rc: list = []
        sock = run_dir / "s.sock"
        # AF_UNIX paths are short (108 bytes): use the path relative to
        # the working directory where the absolute one is too long.
        self.socket_path = str(sock)
        if len(self.socket_path) > 100:
            self.socket_path = os.path.relpath(sock, os.getcwd())
        if len(self.socket_path) > 100:
            raise DaemonStartError(f"socket path too long: {self.socket_path}")

    def start(self, timeout_s: float = 120.0) -> None:
        from adversarial_spec_tpu import cli

        write_registry(self.run_dir, self.serving)
        self.tap.install()

        def serve():
            self._rc.append(cli.main(["serve", "--socket", self.socket_path]))

        self._thread = threading.Thread(target=serve, name="advspec-serve", daemon=True)
        self._thread.start()
        deadline = time.monotonic() + timeout_s
        while not os.path.exists(self.socket_path):
            if not self._thread.is_alive() or time.monotonic() > deadline:
                raise DaemonStartError("the daemon's socket never appeared")
            time.sleep(0.02)

    def client(self, timeout_s: float = 600.0):
        from adversarial_spec_tpu.serve.client import ServeClient

        return ServeClient(self.socket_path, timeout_s=timeout_s)

    def param_bytes(self) -> dict:
        """Bytes of the parameter tree the engine holds, by top-level name
        (`layers.<name>` one level down)."""
        import jax
        from adversarial_spec_tpu.engine import dispatch

        out: dict = {}
        for eng in dispatch.cached_engines():
            for lm in getattr(eng, "_models", {}).values():
                for name, node in lm.params.items():
                    if name == "layers":
                        for sub, leaf in node.items():
                            out[f"layers.{sub}"] = sum(
                                x.nbytes for x in jax.tree.leaves(leaf)
                            )
                    else:
                        out[name] = sum(x.nbytes for x in jax.tree.leaves(node))
        return out

    def stop(self, timeout_s: float = 120.0) -> dict:
        """Drain the daemon, wait for its thread, and report how it went."""
        report = {"drained": False, "rc": None}
        if self._thread is None:
            return report
        try:
            c = self.client(timeout_s=30.0)
            c.drain()
            c.close()
            report["drained"] = True
        except (OSError, TimeoutError) as e:  # the daemon may already be gone
            report["error"] = f"{type(e).__name__}: {e}"
        self._thread.join(timeout=timeout_s)
        report["alive"] = self._thread.is_alive()
        report["rc"] = self._rc[0] if self._rc else None
        self.tap.uninstall()
        return report

    def free_device_state(self) -> None:
        """Drop the program's weights, pools and compiled programs, so that
        the reference has the chip's memory to itself."""
        import jax
        from adversarial_spec_tpu.engine import dispatch

        for eng in dispatch.cached_engines():
            for lm in list(getattr(eng, "_models", {}).values()):
                lm.batcher = None
                lm.params = None
            getattr(eng, "_models", {}).clear()
        dispatch._ENGINE_CACHE.clear()
        gc.collect()
        jax.clear_caches()
        gc.collect()
