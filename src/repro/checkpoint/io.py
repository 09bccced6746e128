"""msgpack-based pytree checkpointing (orbax/flax are not available offline).

Arrays are serialized as (dtype, shape, raw bytes) with zstd compression;
the pytree structure is serialized as a nested msgpack document.  Restore
optionally re-shards onto a ``jax.sharding.NamedSharding`` tree via
``jax.device_put`` (production path), or returns numpy arrays (host path).

``FlatPosterior`` checkpoints (``save_flat_posterior``) are
self-describing: the layout doc (leaf paths/shapes/dtypes/offsets) rides in
the document, so restore needs no ``like`` tree and hands back the exact
[N, P] buffers — no flatten/unflatten round-trip on the save/restore path.

``CheckpointManager`` adds step-numbered directories, retention, and an
atomic-rename commit protocol so a preempted writer never leaves a corrupt
latest checkpoint.
"""
from __future__ import annotations

import os
import shutil
from typing import Any

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard

PyTree = Any

_ARR = "__arr__"
_SCALAR = "__scalar__"


def _pack_leaf(leaf):
    if isinstance(leaf, (jax.Array, np.ndarray)):
        arr = np.asarray(leaf)
        # extension dtypes (bfloat16 and friends) have a lossy numpy byte
        # string ('<V2'): store the NAME, which jnp.dtype round-trips — the
        # bf16-resident gossip history ring checkpoints through here
        dt = arr.dtype
        return {
            _ARR: True,
            "dtype": dt.name if dt.kind == "V" else dt.str,
            "shape": list(arr.shape),
            "data": arr.tobytes(),
        }
    if isinstance(leaf, (int, float, bool, str)) or leaf is None:
        return {_SCALAR: True, "value": leaf}
    raise TypeError(f"unsupported checkpoint leaf type {type(leaf)}")


def _leaf_dtype(tag: str) -> np.dtype:
    """Decode a packed dtype tag: numpy byte strings directly, extension
    dtype NAMES (e.g. 'bfloat16') through jnp.dtype."""
    dt = np.dtype(tag) if not tag[:1].isalpha() else None
    if dt is not None and dt.kind != "V":
        return dt
    return jnp.dtype(tag)


def _unpack_leaf(doc):
    if isinstance(doc, dict) and doc.get(_ARR):
        return np.frombuffer(doc["data"], dtype=_leaf_dtype(doc["dtype"])).reshape(
            doc["shape"]
        )
    if isinstance(doc, dict) and doc.get(_SCALAR):
        return doc["value"]
    return doc


def save_pytree(path: str, tree: PyTree, compress_level: int = 3) -> None:
    leaves, treedef = jax.tree.flatten(tree)
    doc = {
        "treedef": str(treedef),
        "leaves": [_pack_leaf(l) for l in leaves],
    }
    _write_doc(path, doc, compress_level)


def _write_doc(path: str, doc: dict, compress_level: int = 3) -> None:
    raw = msgpack.packb(doc, use_bin_type=True)
    comp = zstandard.ZstdCompressor(level=compress_level).compress(raw)
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "wb") as f:
        f.write(comp)
    os.replace(tmp, path)  # atomic commit


def _read_doc(path: str) -> dict:
    with open(path, "rb") as f:
        raw = zstandard.ZstdDecompressor().decompress(f.read())
    return msgpack.unpackb(raw, raw=False)


def restore_leaf(stored, ref, shard=None):
    """Restore ONE stored leaf into the shape/dtype of reference leaf
    ``ref`` (shared by ``restore_pytree`` and ``api.Session.load`` so there
    is a single restore semantics).  Non-array references pass the stored
    value through; ``shard`` optionally device_puts the result."""
    if isinstance(ref, (jax.Array, np.ndarray, jnp.ndarray)):
        arr = np.asarray(stored)
        if tuple(arr.shape) != tuple(np.shape(ref)):
            raise ValueError(f"shape mismatch: {arr.shape} vs {np.shape(ref)}")
        arr = arr.astype(np.asarray(ref).dtype, copy=False)
        return jax.device_put(arr, shard) if shard is not None else arr
    return stored


def restore_pytree(path: str, like: PyTree, shardings: PyTree | None = None) -> PyTree:
    """Restore into the structure of ``like``.  If ``shardings`` (a pytree of
    jax.sharding.Sharding matching ``like``) is given, leaves are placed
    directly onto devices with those shardings."""
    doc = _read_doc(path)
    leaves = [_unpack_leaf(d) for d in doc["leaves"]]
    like_leaves, treedef = jax.tree.flatten(like)
    if len(leaves) != len(like_leaves):
        raise ValueError(
            f"checkpoint has {len(leaves)} leaves, expected {len(like_leaves)}"
        )
    shard_leaves = (
        treedef.flatten_up_to(shardings) if shardings is not None else [None] * len(leaves)
    )
    out = [
        restore_leaf(stored, ref, shard)
        for stored, ref, shard in zip(leaves, like_leaves, shard_leaves)
    ]
    return jax.tree.unflatten(treedef, out)


_FLAT = "__flat_posterior__"


def save_flat_posterior(path: str, post, compress_level: int = 3) -> None:
    """Checkpoint a ``core.flat.FlatPosterior`` with its layout doc inline.

    The [N, P] mean/rho buffers are written contiguously (no per-leaf
    packing) and the ``FlatLayout`` rides along as a self-describing doc, so
    ``restore_flat_posterior`` needs no ``like`` tree.
    """
    doc = {
        _FLAT: True,
        "layout": post.layout.to_doc(),
        "mean": _pack_leaf(post.mean),
        "rho": _pack_leaf(post.rho),
    }
    _write_doc(path, doc, compress_level)


def restore_flat_posterior(path: str, sharding=None):
    """Restore a ``FlatPosterior`` saved by ``save_flat_posterior``.

    ``sharding`` (optional jax.sharding.Sharding) places both buffers on
    device; otherwise numpy arrays are wrapped as-is.
    """
    from repro.core.flat import FlatLayout, FlatPosterior

    doc = _read_doc(path)
    if not doc.get(_FLAT):
        raise ValueError(f"{path} is not a flat-posterior checkpoint")
    layout = FlatLayout.from_doc(doc["layout"])
    mean = _unpack_leaf(doc["mean"])
    rho = _unpack_leaf(doc["rho"])
    if sharding is not None:
        mean = jax.device_put(mean, sharding)
        rho = jax.device_put(rho, sharding)
    else:
        mean = jnp.asarray(mean)
        rho = jnp.asarray(rho)
    return FlatPosterior(mean=mean, rho=rho, layout=layout)


_SNAPSHOT = "__posterior_snapshot__"


def save_snapshot(path: str, snap, compress_level: int = 3) -> None:
    """Checkpoint a ``serve.PosteriorSnapshot`` next to the session state.

    The (possibly bf16-resident) buffers go through ``_pack_leaf`` — which
    stores extension dtype NAMES, so a narrow snapshot round-trips in its
    resident dtype — and the provenance (window / version / dtype /
    telemetry) rides in the document.  A serving replica restores the exact
    served posterior without any training state."""
    doc = {
        _SNAPSHOT: True,
        "layout": snap.posterior.layout.to_doc(),
        "mean": _pack_leaf(snap.posterior.mean),
        "rho": _pack_leaf(snap.posterior.rho),
        "window": int(snap.window),
        "version": int(snap.version),
        "dtype": snap.dtype,
        "telemetry": snap.telemetry,
    }
    _write_doc(path, doc, compress_level)


def restore_snapshot(path: str):
    """Restore a ``serve.PosteriorSnapshot`` saved by ``save_snapshot``."""
    from repro.core.flat import FlatLayout, FlatPosterior
    from repro.serve.snapshot import PosteriorSnapshot

    doc = _read_doc(path)
    if not doc.get(_SNAPSHOT):
        raise ValueError(f"{path} is not a posterior-snapshot checkpoint")
    post = FlatPosterior(
        mean=jnp.asarray(_unpack_leaf(doc["mean"])),
        rho=jnp.asarray(_unpack_leaf(doc["rho"])),
        layout=FlatLayout.from_doc(doc["layout"]),
    )
    return PosteriorSnapshot(
        posterior=post,
        window=int(doc["window"]),
        version=int(doc["version"]),
        dtype=doc["dtype"],
        telemetry=dict(doc.get("telemetry") or {}),
    )


_SESSION = "__session__"


def save_session(
    path: str,
    spec_doc: dict,
    state,
    *,
    round_idx: int,
    key_data,
    compress_level: int = 3,
) -> None:
    """Self-describing ``api.Session`` checkpoint: the ``ExperimentSpec``
    doc (plain data, see ``ExperimentSpec.to_doc``) rides in the document
    next to the engine-state leaves, so ``restore_session`` +
    ``Session.load`` can rebuild the engine and resume with no ``like``
    tree.  Static state metadata (e.g. the ``FlatLayout``) is NOT stored —
    it is reconstructed by re-building the session from the spec."""
    doc = {
        _SESSION: True,
        "spec": spec_doc,
        "round": int(round_idx),
        "key_data": _pack_leaf(np.asarray(key_data)),
        "leaves": [_pack_leaf(l) for l in jax.tree.leaves(state)],
    }
    _write_doc(path, doc, compress_level)


def restore_session(path: str) -> tuple[dict, list, int, np.ndarray]:
    """-> (spec_doc, state_leaves, round_idx, key_data).  Use
    ``api.Session.load`` for the full rebuild."""
    doc = _read_doc(path)
    if not doc.get(_SESSION):
        raise ValueError(f"{path} is not a session checkpoint")
    leaves = [_unpack_leaf(d) for d in doc["leaves"]]
    return doc["spec"], leaves, doc["round"], np.asarray(_unpack_leaf(doc["key_data"]))


class CheckpointManager:
    """Step-numbered checkpoints with retention and atomic commit."""

    def __init__(self, root: str, max_to_keep: int = 3):
        self.root = root
        self.max_to_keep = max_to_keep
        os.makedirs(root, exist_ok=True)

    def _step_path(self, step: int) -> str:
        return os.path.join(self.root, f"step_{step:09d}.ckpt")

    def save(self, step: int, tree: PyTree) -> str:
        path = self._step_path(step)
        save_pytree(path, tree)
        self._gc()
        return path

    def all_steps(self) -> list[int]:
        steps = []
        for name in os.listdir(self.root):
            if name.startswith("step_") and name.endswith(".ckpt"):
                steps.append(int(name[len("step_"):-len(".ckpt")]))
        return sorted(steps)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, like: PyTree, step: int | None = None, shardings=None) -> tuple[int, PyTree]:
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.root}")
        return step, restore_pytree(self._step_path(step), like, shardings)

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.max_to_keep]:
            p = self._step_path(s)
            if os.path.isdir(p):
                shutil.rmtree(p)
            else:
                os.remove(p)
