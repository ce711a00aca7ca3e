"""Checkpoint / resume via orbax (async, sharded-native).

Reference parity: HF Trainer `save_steps` checkpoints + DeepSpeed ZeRO
per-rank partitioned state + `zero_to_fp32.py` consolidation +
`safe_save_model_for_hf_trainer` / projector-only partial saves
(SURVEY.md §5 "Checkpoint / resume"). Orbax writes sharded arrays
natively, so there is no consolidation step; interop with reference
checkpoints goes through models/import_hf (safetensors import/export).
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable

import jax
import numpy as np
import orbax.checkpoint as ocp

from oryx_tpu.utils import faults
from oryx_tpu.utils.retry import BackoffPolicy, retry_call

Params = dict[str, Any]


# Leaf marker for restore_partial targets: "do not restore this leaf".
PLACEHOLDER = ocp.PLACEHOLDER


class CheckpointManager:
    """Async step-numbered checkpoints with retention, plus resume.

    Failure containment: orbax itself writes each step into a temp
    location and renames on finalize (a torn write can never become
    "latest"); on top of that, `save` retries transient failures with
    bounded exponential backoff (`save_retry`) — and a persistent
    failure still fails loudly after the budget. Scope honestly: the
    retry wraps the SYNCHRONOUS phase of an async save (directory
    prep, serialization enqueue — and the `checkpoint_save` chaos
    site). A failure in the background commit thread surfaces on the
    NEXT save()/wait() call; the next save runs under this same
    policy, so a transient background failure costs at most the one
    torn checkpoint (which temp+rename keeps out of "latest") rather
    than the run. `save_retries` counts the recoveries for
    telemetry/tests. `sleep` is injectable so tests pin the schedule
    without wall-clock waits."""

    def __init__(self, directory: str, *, max_to_keep: int = 3,
                 save_retry: BackoffPolicy | None = None,
                 sleep: Callable[[float], None] = time.sleep) -> None:
        self.directory = os.path.abspath(directory)
        self._save_retry = save_retry or BackoffPolicy(
            retries=3, base_s=0.5, factor=2.0, max_s=10.0
        )
        self._sleep = sleep
        self.save_retries = 0
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True,
                enable_async_checkpointing=True,
            ),
            # Register the handler up front so `item_metadata` works on a
            # fresh manager (without it, metadata() returns None until a
            # save has happened in-process).
            item_handlers=ocp.StandardCheckpointHandler(),
        )

    def save(self, step: int, state: Any, *, force: bool = False) -> bool:
        """Async-save a pytree (TrainState or bare params), retrying
        transient failures per `save_retry`. The chaos site
        `checkpoint_save` injects failures HERE, before orbax runs, so
        the retry schedule is exercised deterministically."""

        def attempt() -> bool:
            faults.fault_point("checkpoint_save")
            return self._mgr.save(
                step, args=ocp.args.StandardSave(state), force=force
            )

        def count(_attempt, _exc, _delay) -> None:
            self.save_retries += 1

        return retry_call(
            attempt, policy=self._save_retry, retry_on=(Exception,),
            sleep=self._sleep, on_retry=count,
            describe=f"checkpoint save (step {step})",
        )

    def restore(self, state_like: Any = None, step: int | None = None) -> Any:
        """Restore into the structure/shardings of `state_like` (an
        abstract or concrete pytree of the same shape). With state_like=None,
        restores the checkpoint's own saved structure (host numpy)."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.directory}")
        # Chaos site: restore failure — the resume path's caller (or
        # the operator) decides whether an older step is acceptable.
        faults.fault_point("checkpoint_restore")
        if state_like is None:
            return self._mgr.restore(step)
        restored = self._mgr.restore(
            step, args=ocp.args.StandardRestore(state_like)
        )
        # Older orbax restores on the default device and silently drops
        # the template's shardings; re-place any leaf whose sharding
        # disagrees with the target (no-op copy-wise on new orbax).
        # Single-device template leaves (step counters, optax schedule
        # counts) were UNCOMMITTED arrays; orbax hands back committed
        # ones, which jit refuses to mix with multi-device args —
        # rebuild those uncommitted.
        from jax.sharding import SingleDeviceSharding

        def place(t, r):
            want = getattr(t, "sharding", None)
            if want is None or not hasattr(r, "sharding"):
                return r
            if r.sharding != want:
                return jax.device_put(r, want)
            if isinstance(want, SingleDeviceSharding):
                return jax.numpy.asarray(np.asarray(r))
            return r

        return jax.tree.map(place, state_like, restored)

    def restore_partial(self, target: Any, step: int | None = None) -> Any:
        """Restore only the non-PLACEHOLDER leaves of `target` (abstract
        arrays, optionally with shardings so shards land straight on
        their devices); `ocp.PLACEHOLDER` leaves are never read from
        disk. The Standard handler rejects placeholders, so this goes
        through the underlying PyTree layer."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.directory}")
        path = os.path.join(self.directory, str(step), "default")

        # PyTreeRestore takes placement from restore_args, NOT from the
        # target's ShapeDtypeStruct.sharding (which it silently ignores,
        # restoring with the save-time sharding instead).
        def rargs(leaf):
            if isinstance(leaf, jax.ShapeDtypeStruct):
                return ocp.ArrayRestoreArgs(
                    sharding=leaf.sharding, global_shape=leaf.shape,
                    dtype=leaf.dtype,
                )
            return ocp.RestoreArgs()

        return ocp.PyTreeCheckpointer().restore(
            path,
            args=ocp.args.PyTreeRestore(
                item=target, restore_args=jax.tree.map(rargs, target)
            ),
        )

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def metadata(self, step: int | None = None) -> Any:
        """Saved-tree structure as abstract leaves (shape/dtype, no data)
        — the basis for building a sharded restore target without ever
        materializing the checkpoint on host."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.directory}")
        meta = self._mgr.item_metadata(step)
        if meta is None:
            raise RuntimeError(
                f"no item metadata for step {step} in {self.directory}"
            )
        return meta

    def wait(self) -> None:
        """Block until pending async saves finish."""
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()


def _npz_path(path: str) -> str:
    """np.savez appends '.npz' when missing but np.load does not; normalize
    so save/load round-trip on the same argument."""
    return path if path.endswith(".npz") else path + ".npz"


def save_projector_only(path: str, params: Params) -> None:
    """Stage-1-style partial checkpoint: compressor/projector weights only
    (the reference's `mm_projector.bin` analog), as a flat npz.

    Atomic: written to a temp sibling then os.replace'd, so a crash
    mid-write can never leave a torn file at the published path."""
    flat = jax.tree_util.tree_flatten_with_path(params["compressor"])[0]
    arrays = {
        "/".join(p.key for p in path): np.asarray(leaf)
        for path, leaf in flat
    }
    final = _npz_path(path)
    os.makedirs(os.path.dirname(os.path.abspath(final)), exist_ok=True)
    tmp = final + ".tmp"
    try:
        np.savez(tmp, **arrays)
        # np.savez may append .npz to the temp name too; normalize.
        written = tmp if os.path.exists(tmp) else _npz_path(tmp)
        os.replace(written, final)
    finally:
        for leftover in (tmp, _npz_path(tmp)):
            if os.path.exists(leftover):
                os.remove(leftover)


def load_projector_only(path: str, params: Params) -> Params:
    """Merge a projector-only checkpoint into a full param tree (the
    reference's `pretrain_mm_mlp_adapter` load path, SURVEY.md §3.3)."""
    data = np.load(_npz_path(path))
    comp = params["compressor"]

    def fill(path, leaf):
        key = "/".join(p.key for p in path)
        if key in data:
            arr = data[key]
            assert arr.shape == leaf.shape, (key, arr.shape, leaf.shape)
            return jax.numpy.asarray(arr, dtype=leaf.dtype)
        return leaf

    new_comp = jax.tree_util.tree_map_with_path(fill, comp)
    return {**params, "compressor": new_comp}
