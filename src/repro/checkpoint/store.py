"""Sharded checkpoint store: atomic manifests, async saves, resume.

Fault-tolerance contract (the substrate elastic repack and multi-thousand-
node posture rely on):

  * a checkpoint is VALID iff its ``manifest.json`` exists — the manifest is
    written LAST and renamed into place atomically, so a writer killed
    mid-save never leaves a readable-but-corrupt step;
  * array leaves are saved per-shard: each host writes only the shards it
    owns (``leaf.addressable_shards``), so save bandwidth scales with hosts
    and no host needs global-array RAM (on this single-host container that
    degenerates to one shard per leaf — the layout is identical);
  * saves can run on a background thread (``async_save=True``): the train
    loop donates nothing, since leaves are device->host copied before the
    thread starts, and the previous async save is joined before a new one
    begins (bounded memory);
  * ``restore`` reassembles leaves and (optionally) re-shards them onto a
    *different* mesh — the elastic-repack path: a job killed on a 2g
    instance resumes on a 3g instance from the same files;
  * integrity: every shard file carries a crc32 in the manifest, checked on
    restore;
  * one format whatever the state's layout: a tree stored stacked
    (``runtime.train_step.StackedState``, which has ``unstacked`` and
    ``restack``) is written as its per-leaf tree and stacked again on
    restore, so its files are those of the per-leaf state.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
import zlib
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

MANIFEST = "manifest.json"


def _path_entry(p) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(p, attr):
            return str(getattr(p, attr))
    return str(p)


def _leaf_paths(tree) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(_path_entry(p) for p in path), leaf) for path, leaf in flat]


def _tree_def(tree):
    return jax.tree_util.tree_structure(tree)


@dataclasses.dataclass
class CheckpointInfo:
    step: int
    path: Path
    wall_time: float


class CheckpointStore:
    """Directory layout: <root>/step_<n>/{leaf files, manifest.json}."""

    def __init__(self, root: str | Path, *, keep: int = 3):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._async_thread: Optional[threading.Thread] = None

    # -- save -------------------------------------------------------------------

    def save(self, step: int, tree, *, extra: Optional[Dict] = None,
             async_save: bool = False) -> Path:
        """Save ``tree`` (pytree of jax/np arrays) at ``step``."""
        self.wait()  # join any in-flight async save (bounded memory)
        if hasattr(tree, "unstacked"):
            tree = tree.unstacked()
        # device->host copy NOW so the caller may donate/mutate afterwards
        host_leaves = []
        for key, leaf in _leaf_paths(tree):
            if isinstance(leaf, jax.Array):
                shards = [
                    (i, np.asarray(s.data)) for i, s in enumerate(leaf.addressable_shards)
                ]
            else:
                # snapshot semantics: np leaves must be COPIED, or an async
                # writer would observe later caller mutations
                shards = [(0, np.array(leaf, copy=True))]
            host_leaves.append((key, leaf, shards))

        if async_save:
            t = threading.Thread(
                target=self._write, args=(step, tree, host_leaves, extra), daemon=True
            )
            t.start()
            self._async_thread = t
            return self.root / f"step_{step:08d}"
        return self._write(step, tree, host_leaves, extra)

    def _write(self, step, tree, host_leaves, extra) -> Path:
        final = self.root / f"step_{step:08d}"
        tmp = self.root / f".tmp_step_{step:08d}"
        if tmp.exists():
            for f in tmp.iterdir():
                f.unlink()
        tmp.mkdir(parents=True, exist_ok=True)

        leaves_meta = []
        for key, leaf, shards in host_leaves:
            fname = key.replace("/", "__") + ".npy"
            shard_meta = []
            for idx, arr in shards:
                sf = f"{fname}.shard{idx}" if len(shards) > 1 else fname
                with open(tmp / sf, "wb") as f:
                    np.save(f, arr)
                shard_meta.append(
                    {"file": sf, "index": idx, "crc32": zlib.crc32(arr.tobytes())}
                )
            leaves_meta.append(
                {
                    "key": key,
                    "shape": list(np.shape(leaf)),
                    "dtype": str(np.asarray(shards[0][1]).dtype),
                    "shards": shard_meta,
                }
            )
        manifest = {
            "step": step,
            "wall_time": time.time(),
            "leaves": leaves_meta,
            "extra": extra or {},
        }
        # manifest LAST + atomic rename = crash consistency
        mtmp = tmp / (MANIFEST + ".tmp")
        mtmp.write_text(json.dumps(manifest, indent=1))
        mtmp.rename(tmp / MANIFEST)
        if final.exists():
            import shutil

            shutil.rmtree(final)
        tmp.rename(final)
        self._gc()
        return final

    def wait(self):
        if self._async_thread is not None:
            self._async_thread.join()
            self._async_thread = None

    def _gc(self):
        ckpts = self.list()
        for info in ckpts[: -self.keep]:
            import shutil

            shutil.rmtree(info.path, ignore_errors=True)

    # -- restore -----------------------------------------------------------------

    def list(self) -> List[CheckpointInfo]:
        out = []
        for d in sorted(self.root.glob("step_*")):
            man = d / MANIFEST
            if not man.exists():
                continue  # incomplete save — invisible by contract
            meta = json.loads(man.read_text())
            out.append(CheckpointInfo(meta["step"], d, meta["wall_time"]))
        return out

    def latest_step(self) -> Optional[int]:
        ckpts = self.list()
        return ckpts[-1].step if ckpts else None

    def restore(
        self, tree_like, step: Optional[int] = None, *, shardings=None
    ) -> Tuple[Any, Dict]:
        """Restore into the structure of ``tree_like``.

        ``shardings``: optional pytree of NamedSharding to place leaves onto
        (may describe a different mesh than the one that saved — elastic
        resume). Returns (tree, extra).
        """
        if hasattr(tree_like, "restack"):
            per_leaf, extra = self.restore(
                jax.eval_shape(lambda t: t.unstacked(), tree_like), step)
            tree = tree_like.restack(per_leaf)
            return (tree if shardings is None else jax.device_put(tree, shardings)), extra
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints under {self.root}")
        d = self.root / f"step_{step:08d}"
        meta = json.loads((d / MANIFEST).read_text())
        by_key = {m["key"]: m for m in meta["leaves"]}

        keys = [k for k, _ in _leaf_paths(tree_like)]
        sh_leaves = (
            [s for _, s in _leaf_paths(shardings)] if shardings is not None else [None] * len(keys)
        )
        leaves = []
        for key, sh in zip(keys, sh_leaves):
            m = by_key[key]
            parts = []
            for smeta in sorted(m["shards"], key=lambda s: s["index"]):
                with open(d / smeta["file"], "rb") as f:
                    arr = np.load(f)
                if zlib.crc32(arr.tobytes()) != smeta["crc32"]:
                    raise IOError(f"crc mismatch in {d / smeta['file']}")
                if arr.dtype.kind == "V":
                    # ml_dtypes (bfloat16 etc.) round-trip through np.save as
                    # raw void bytes — reinterpret via the manifest dtype.
                    import ml_dtypes

                    arr = arr.view(np.dtype(getattr(ml_dtypes, m["dtype"])))
                parts.append(arr)
            if len(parts) == 1:
                full = parts[0]
            else:
                # single-host reassembly: shards were equal splits on axis 0
                full = np.concatenate(parts, axis=0)
            if list(full.shape) != m["shape"]:
                full = full.reshape(m["shape"])
            if sh is not None:
                leaves.append(jax.device_put(full, sh))
            else:
                leaves.append(jnp.asarray(full))
        tree = jax.tree_util.tree_unflatten(_tree_def(tree_like), leaves)
        return tree, meta.get("extra", {})
