"""Native checkpoint cache: Orbax save/load of converted param pytrees.

SURVEY §5 (checkpoint/resume): the reference's model-side "checkpointing"
obligation is checkpoint *loading* — here HF safetensors convert once into
the layer-stacked native layout and are cached via Orbax, so subsequent
engine starts restore directly into the target shardings (no per-layer
stacking, no transposes, no torch-layout work). The debate-state tier
(sessions/round snapshots, debate/session.py) is unchanged and independent.

Cache location: ``<checkpoint_dir>/.native-cache/<fingerprint>`` beside the
HF checkpoint, fingerprinted by family/size/dtype/quant — plus the
transposed-head flag when the config ties embeddings (the flag adds an
``lm_head_t`` leaf, i.e. changes the pytree layout) — so neither a config
change nor an env toggle ever reads a stale layout.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import uuid
from pathlib import Path

import jax


def _source_stat(checkpoint: str) -> list:
    """Cheap identity of the source weights: (name, size, mtime_ns) of
    every safetensors/index file — no content read. Replacing the weights
    in place (fine-tune update) therefore changes the fingerprint."""
    ckpt = Path(checkpoint)
    entries = []
    for pattern in ("*.safetensors", "*.safetensors.index.json"):
        for f in sorted(ckpt.glob(pattern)):
            st = f.stat()
            entries.append([f.name, st.st_size, st.st_mtime_ns])
    return entries


def transposed_head_flag() -> bool:
    """ONE reading of ADVSPEC_TRANSPOSED_HEAD (default on) — the cache
    fingerprint, the restore template, and the HF loader must all parse
    it identically or caches thrash (save one layout, template another)."""
    return os.environ.get("ADVSPEC_TRANSPOSED_HEAD", "1") != "0"


def cache_dir_for(
    checkpoint: str,
    family: str,
    size: str,
    dtype: str,
    quant: str = "",
    tied_embeddings: bool = False,
    n_layers: int = 0,
) -> Path:
    # For tied-embedding configs the transposed-head flag changes the
    # pytree LAYOUT (extra lm_head_t leaf), so it must be part of the
    # fingerprint: toggling ADVSPEC_TRANSPOSED_HEAD must select a
    # different cache dir, not thrash or silently serve the old layout.
    # Untied configs have identical layout under both flag values — keep
    # their fingerprint flag-independent (no spurious reconversion).
    t_head = tied_embeddings and transposed_head_flag()
    identity = [family, size, dtype, quant, int(t_head), _source_stat(checkpoint)]
    if n_layers:  # a depth-cut entry (registry ModelSpec.n_layers)
        identity.append(n_layers)
    fingerprint = hashlib.sha1(json.dumps(identity).encode()).hexdigest()[:12]
    return Path(checkpoint) / ".native-cache" / fingerprint


def _sweep_stale_tmp(cache_parent: Path, max_age_s: float = 86400.0) -> None:
    """Remove abandoned writer tmp dirs (``*.tmp-<pid>-<hex>``).

    A process killed mid-save (daemon prefetch thread at interpreter
    exit, OOM-kill) leaves its multi-GB tmp dir behind —
    its finally never runs. Each new writer sweeps siblings older than
    a day: old enough that no live writer (saves take minutes, not
    days) can be holding them. Best-effort; errors never block a save.
    """
    import time as _time

    try:
        now = _time.time()
        for entry in cache_parent.iterdir():
            if ".tmp-" in entry.name and entry.is_dir():
                try:
                    if now - entry.stat().st_mtime > max_age_s:
                        shutil.rmtree(entry, ignore_errors=True)
                except OSError:
                    pass
    except OSError:
        pass


def save_native(params, cache_dir: Path) -> None:
    """Write the converted pytree atomically.

    Per-writer unique tmp dir + rename: concurrent cold-cache processes
    (multi-opponent CLIs, one process per host on a pod) never see each
    other's partial writes, and whichever rename lands first wins.
    """
    import orbax.checkpoint as ocp

    cache_dir = Path(cache_dir)
    cache_dir.parent.mkdir(parents=True, exist_ok=True)
    _sweep_stale_tmp(cache_dir.parent)
    tmp = cache_dir.with_name(
        f"{cache_dir.name}.tmp-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    )
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(tmp.resolve(), params)
    try:
        tmp.rename(cache_dir)
    except OSError:
        if cache_dir.exists():  # another writer won the race — fine
            shutil.rmtree(tmp, ignore_errors=True)
        else:
            raise


def load_native(cache_dir: Path, like_params):
    """Restore into the shardings/dtypes of ``like_params`` (an abstract
    pytree of jax.ShapeDtypeStruct with shardings is enough)."""
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(Path(cache_dir).resolve(), like_params)


def has_native(cache_dir: Path) -> bool:
    return Path(cache_dir).is_dir()


def abstract_like(params):
    """ShapeDtypeStruct pytree (with shardings) describing ``params``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None)
        ),
        params,
    )
