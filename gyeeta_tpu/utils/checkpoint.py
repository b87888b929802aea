"""Checkpoint/restore of engine state (sketch snapshots).

The reference has no process checkpointing — durable state is Postgres and
agents resend inventory on reconnect (SURVEY §5). The TPU tier adds real
checkpoints: AggState is one pytree of arrays, so a snapshot is an
``npz`` with the flattened leaves plus a config fingerprint; restore
refuses a mismatched geometry instead of silently mis-slicing HBM.
Recovery composes both: restore the sketch snapshot, then replay from
agents/history for the gap.
"""

from __future__ import annotations

import hashlib
import json
import pathlib

import jax
import numpy as np


def _cfg_fingerprint(cfg) -> str:
    # repr-text equality: any geometry field change invalidates restores
    return hashlib.sha256(repr(cfg).encode()).hexdigest()[:16]


def save(path, cfg, state, extra: dict | None = None) -> pathlib.Path:
    """Write state pytree → ``<path>`` (npz). Atomic AND durable:
    tmp + fsync(file) + rename + fsync(dir). Without the fsyncs a
    crash (or power loss) shortly after the rename can leave the
    NEWEST checkpoint torn on disk — exactly the file a supervised
    ``--restore-latest`` restart reaches for first (the walk-back in
    ``server_main.checkpoint_candidates`` then lands on the next-older
    one, but a torn newest should be the rare case, not the norm)."""
    import os

    path = pathlib.Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    leaves, treedef = jax.tree_util.tree_flatten(state)
    payload = {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}
    payload["__meta__"] = np.frombuffer(json.dumps({
        "nleaves": len(leaves),
        "cfg": _cfg_fingerprint(cfg),
        "extra": extra or {},
    }).encode(), dtype=np.uint8)
    tmp = path.with_suffix(".tmp.npz")
    with open(tmp, "wb") as f:
        np.savez_compressed(f, **payload)
        f.flush()
        os.fsync(f.fileno())      # file contents durable BEFORE rename
    tmp.rename(path)
    try:                          # …and the rename itself durable
        dfd = os.open(path.parent, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:               # pragma: no cover — exotic fs
        pass
    # a crash between open(tmp) and the rename strands a .tmp.npz; left
    # alone they accumulate forever in the checkpoint dir. Each
    # SUCCESSFUL save sweeps siblings (its own tmp was just renamed
    # away, so anything still matching is a previous crash's orphan).
    sweep_stale_tmp(path.parent)
    return path


def sweep_stale_tmp(ckpt_dir) -> int:
    """Delete ``*.tmp.npz`` staging orphans left by a crash
    mid-:func:`save`. Called on daemon start and after each successful
    save; never touches completed checkpoints (the
    ``checkpoint_candidates`` walk already excludes tmp files, so this
    is disk hygiene, not correctness). Returns files removed."""
    import os as _os

    n = 0
    d = pathlib.Path(ckpt_dir)
    if not d.is_dir():
        return 0
    for p in d.glob("*.tmp.npz"):
        try:
            _os.unlink(p)
            n += 1
        except OSError:           # pragma: no cover — already gone
            pass
    return n


def restore(path, cfg, like, flat_widths: dict | None = None):
    """Read a checkpoint into the structure of ``like`` (same treedef).

    ``flat_widths`` maps the path of each leaf stored flat, (..., S·W),
    to its row width W (``aggstate.flat_widths``): a checkpoint that
    holds such a leaf in its logical (..., S, W) shape, as one written
    before the leaf was stored flat, is reshaped on load. Raises
    ValueError on config-fingerprint or any other leaf-shape mismatch.
    Returns (state, extra_dict).
    """
    path = pathlib.Path(path)
    flat_widths = flat_widths or {}
    with np.load(path) as z:
        meta = json.loads(bytes(z["__meta__"]).decode())
        if meta["cfg"] != _cfg_fingerprint(cfg):
            raise ValueError(
                f"checkpoint config fingerprint {meta['cfg']} does not "
                f"match engine config {_cfg_fingerprint(cfg)}")
        leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
        if meta["nleaves"] != len(leaves):
            raise ValueError(
                f"checkpoint has {meta['nleaves']} leaves, engine state "
                f"has {len(leaves)} — incompatible versions")
        new_leaves = []
        for i, (kp, ref) in enumerate(leaves):
            arr = z[f"leaf_{i}"]
            w = flat_widths.get(
                jax.tree_util.keystr(kp, simple=True, separator="."))
            if w and arr.shape == ref.shape[:-1] + (ref.shape[-1] // w, w):
                arr = arr.reshape(ref.shape)
            if arr.shape != ref.shape:
                raise ValueError(
                    f"leaf {i}: checkpoint shape {arr.shape} != "
                    f"state shape {ref.shape}")
            new_leaves.append(arr.astype(ref.dtype))
        return (jax.tree_util.tree_unflatten(treedef, new_leaves),
                meta["extra"])
