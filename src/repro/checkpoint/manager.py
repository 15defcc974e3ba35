"""Atomic, elastic checkpointing for fault-tolerant training.

Properties required at 1000+ nodes, all present here in miniature:

* **atomicity** — write to ``step_XXXX.tmp`` then ``os.replace`` so a
  crash mid-save never corrupts the latest-good checkpoint;
* **elastic restore** — arrays are saved topology-free (host numpy) and
  restored via ``device_put`` onto *whatever* mesh/shardings the new job
  uses — a 512-chip checkpoint restores onto 256 chips (tests exercise a
  mesh change);
* **step-resumable data** — the data pipeline is (seed, step)-pure, so
  storing the step counter alone resumes the exact token stream;
* **retention** — keeps the newest ``keep`` checkpoints.

At real scale the host-gather becomes per-shard writes into a parallel
store (tensorstore/OCDBT); the manager interface (save/restore/latest)
is the part the rest of the framework depends on and stays unchanged.
"""
from __future__ import annotations

import json
import os
import shutil
import zipfile

import jax
import numpy as np

from repro.core.errors import CheckpointCorruptError
from repro.core.faults import fault_hook

#: error classes that mean "this step's files are unreadable" (truncated
#: zip, torn JSON, missing member) as opposed to a caller bug
_CORRUPT_ERRORS = (OSError, ValueError, KeyError, EOFError,
                   json.JSONDecodeError, zipfile.BadZipFile)


def _fsync_file(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:            # pragma: no cover - exotic filesystems
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _flatten(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    keys = ["/".join(str(k) for k in path) for path, _ in flat]
    vals = [v for _, v in flat]
    return keys, vals, treedef


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)

    # -- paths -------------------------------------------------------------

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:08d}")

    def all_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and not name.endswith(".tmp"):
                try:
                    out.append(int(name.split("_")[1]))
                except ValueError:
                    pass
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    # -- save / restore ------------------------------------------------------

    def save(self, step: int, state, extra: dict | None = None) -> str:
        """Persist ``state`` (any pytree) atomically as step ``step``.

        ``extra`` — optional JSON-serializable dict stored in the step's
        ``meta.json`` (fingerprints, provenance); read it back with
        ``read_meta(step)["extra"]``.

        Crash-safety contract: the tmp dir is fully written AND fsynced
        (files + directory entry) before the single ``os.replace`` that
        publishes it, and an existing step is moved aside — never
        rmtree'd — before the replace, so at every instant the directory
        holds at least one intact copy of the newest successfully-saved
        step.  A kill at ANY point leaves either the old step, the new
        step, or a ``.tmp``/``.old`` leftover that resume ignores.
        """
        keys, vals, _ = _flatten(state)
        tmp = self._step_dir(step) + ".tmp"
        final = self._step_dir(step)
        shutil.rmtree(tmp, ignore_errors=True)   # clobber a stale tmp
        os.makedirs(tmp)
        arrays = {}
        for k, v in zip(keys, vals):
            a = np.asarray(jax.device_get(v))
            if a.dtype.kind == "V" or a.dtype.name == "bfloat16":
                # npz can't serialize ml_dtypes; bf16 -> f32 is lossless
                # and restore casts back to the target dtype.
                import jax.numpy as jnp
                a = np.asarray(jnp.asarray(v).astype(jnp.float32))
            arrays[k] = a
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        meta = {"step": step, "keys": keys}
        if extra is not None:
            meta["extra"] = extra
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        _fsync_file(os.path.join(tmp, "arrays.npz"))
        _fsync_dir(tmp)
        # chaos site: the injection point for "crashed after writing the
        # tmp but before publishing" — the window atomicity must cover
        fault_hook("checkpoint_write", None)
        old = None
        if os.path.exists(final):
            # move the previous copy aside instead of deleting it: the
            # old rmtree-then-replace left a window with NO intact copy
            old = final + ".old"
            shutil.rmtree(old, ignore_errors=True)
            os.replace(final, old)
        os.replace(tmp, final)          # atomic publish
        _fsync_dir(self.dir)
        if old is not None:
            shutil.rmtree(old, ignore_errors=True)
        self._gc()
        return final

    def read_meta(self, step: int) -> dict:
        """The step's ``meta.json`` (step number, leaf keys, ``extra``).

        A missing/torn/unparseable file raises ``CheckpointCorruptError``
        so resume can quarantine the step and fall back."""
        path = os.path.join(self._step_dir(step), "meta.json")
        try:
            with open(path) as f:
                meta = json.load(f)
        except _CORRUPT_ERRORS as e:
            raise CheckpointCorruptError(
                f"step {step}: unreadable meta.json at {path!r} "
                f"({type(e).__name__}: {e})") from e
        if not isinstance(meta, dict) or "keys" not in meta:
            raise CheckpointCorruptError(
                f"step {step}: meta.json at {path!r} parsed but is not a "
                f"checkpoint manifest (missing 'keys')")
        return meta

    def quarantine(self, step: int) -> str:
        """Move a corrupt step OUT of the resume path — renamed to
        ``step_XXXXXXXX.corrupt`` (suffix-numbered on collision) so the
        evidence survives for forensics but ``all_steps`` never offers
        it again.  Returns the quarantine path."""
        src = self._step_dir(step)
        dst = src + ".corrupt"
        i = 1
        while os.path.exists(dst):
            dst = f"{src}.corrupt{i}"
            i += 1
        os.replace(src, dst)
        return dst

    def restore(self, step: int, like, shardings=None):
        """Restore into the structure of ``like`` (a matching pytree).

        ``shardings`` — optional matching pytree of NamedShardings for the
        *target* mesh (elastic restore onto a different topology).
        """
        path = self._step_dir(step)
        try:
            with np.load(os.path.join(path, "arrays.npz")) as data:
                keys, vals, treedef = _flatten(like)
                restored = []
                for k, v in zip(keys, vals):
                    arr = data[k]
                    restored.append(arr)
        except _CORRUPT_ERRORS as e:
            raise CheckpointCorruptError(
                f"step {step}: unreadable arrays.npz under {path!r} "
                f"({type(e).__name__}: {e}) — truncated write or disk "
                f"corruption") from e
        tree = jax.tree.unflatten(treedef, restored)
        if shardings is not None:
            tree = jax.tree.map(
                lambda a, s: jax.device_put(a, s), tree, shardings)
        else:
            # cast via jnp: numpy lacks native bf16 cast paths (ml_dtypes).
            # The round-trip is container-preserving: numpy template
            # leaves restore as numpy, jax leaves as device arrays (the
            # host-resident solver states depend on it).
            import jax.numpy as jnp

            def _leaf(a, v):
                if isinstance(v, (np.ndarray, np.generic)):
                    dt = np.dtype(v.dtype)
                    if dt.kind == "V" or dt.name == "bfloat16":
                        return np.asarray(jnp.asarray(a).astype(dt))
                    return np.asarray(a).astype(dt)  # stays 64-bit safe
                return jax.device_put(jnp.asarray(a).astype(v.dtype))

            tree = jax.tree.map(_leaf, tree, like)
        return tree

    def restore_latest(self, like, shardings=None):
        step = self.latest_step()
        if step is None:
            return None, None
        return step, self.restore(step, like, shardings)

    def _gc(self):
        steps = self.all_steps()
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
