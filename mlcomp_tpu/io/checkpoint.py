"""Checkpoint save/restore over orbax.

The reference checkpoints torch state_dicts to host disk; here the whole
TrainState pytree (params, BN stats, optimizer state, step) goes through
orbax — which handles sharded arrays natively, so the same call works
single-chip and under a multi-host mesh (each host writes its shards).
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp


def _mgr(
    directory: Path, max_to_keep: int = 3, async_save: bool = False
) -> ocp.CheckpointManager:
    return ocp.CheckpointManager(
        directory,
        options=ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            create=True,
            enable_async_checkpointing=async_save,
        ),
    )


class AsyncCheckpointWriter:
    """Long-lived manager whose saves overlap training.

    ``save_checkpoint`` opens a manager, writes, and blocks per call —
    right for one-shot saves.  The epoch loop wants the opposite: orbax's
    async path snapshots device arrays to host memory before returning
    (donation-safe — the next train step may overwrite the HBM buffers
    immediately) and streams to disk on a background thread, so epoch
    k+1 computes while epoch k's checkpoint lands.  ``wait()`` joins
    outstanding writes; ALWAYS ``close()`` before reading
    ``latest_step``/``restore_checkpoint`` on the same directory.
    """

    def __init__(self, directory: str | Path, max_to_keep: int = 3):
        self.directory = Path(directory).absolute()
        self._mgr = _mgr(self.directory, max_to_keep, async_save=True)

    def save(self, state: Any, step: int) -> None:
        self._mgr.save(int(step), args=ocp.args.StandardSave(state))

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self.wait()
        self._mgr.close()

    def __enter__(self) -> "AsyncCheckpointWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def save_checkpoint(
    directory: str | Path, state: Any, step: int, max_to_keep: int = 3
) -> str:
    """Save a pytree; returns the checkpoint path."""
    directory = Path(directory).absolute()
    with _mgr(directory, max_to_keep) as mgr:
        mgr.save(step, args=ocp.args.StandardSave(state))
        mgr.wait_until_finished()
    return str(directory / str(step))


def latest_step(directory: str | Path) -> Optional[int]:
    directory = Path(directory).absolute()
    if not directory.exists():
        return None
    with _mgr(directory) as mgr:
        return mgr.latest_step()


def _saved_tree(directory: Path, step: int) -> dict:
    """Metadata tree of the state saved at ``step``: which top-level
    keys exist (``ema_params`` is None when the run tracked no EMA) and
    every leaf's shape/dtype — read without touching array bytes."""
    meta = ocp.StandardCheckpointer().metadata(
        Path(directory) / str(step) / "default"
    )
    return meta.item_metadata.tree


def _weight_keys(saved: dict) -> list:
    """The top-level keys of a saved state that an eval consumer reads:
    the EMA weights when the run tracked them (they ARE the eval
    weights; the raw params are then not read at all), else the params;
    plus ``model_state`` / ``step`` where the checkpoint really holds
    them — a partial restore hands back the item's own values for a key
    it does not find, so only present keys may be asked for."""
    keys = ["ema_params" if saved.get("ema_params") else "params"]
    return keys + [
        k for k in ("model_state", "step") if saved.get(k) not in (None, {})
    ]


def restore_eval_state(directory: str | Path, state: Any, step: Optional[int] = None):
    """Weights-only restore for eval/infer/generate tasks.

    Reads only the weight subtrees, so the on-disk optimizer state —
    whose structure depends on the TRAIN task's optimizer config (adamw
    + grad-clip chains etc.) — is never read instead of failing the
    structure match.  Downstream stages therefore never need to repeat
    the train stage's optimizer config.  When the checkpoint carries EMA
    weights they become the restored params (same policy as
    ``restore_checkpoint`` grafting into a non-EMA target).  Restored
    arrays are placed onto the shardings of ``state``'s arrays.
    """
    from orbax.checkpoint import checkpoint_utils

    directory = Path(directory).absolute()
    with _mgr(directory) as mgr:
        step = step if step is not None else mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")

        template = {
            "params": state.params, "ema_params": state.params,
            "model_state": state.model_state, "step": state.step,
        }
        item = {
            k: template[k] for k in _weight_keys(_saved_tree(directory, step))
        }
        # partial restore: saved keys the item does not name (opt_state
        # — potentially several times the param bytes) are never read,
        # and restored arrays land directly on the shardings of the
        # item's arrays.  A 1.2B model beside its own template leaves no
        # room on a 16 GB chip for anything else, so there is no
        # untargeted full-read fallback: it would put the optimizer
        # state on the device too.
        raw = mgr.restore(step, args=ocp.args.PyTreeRestore(
            item=item,
            restore_args=checkpoint_utils.construct_restore_args(item),
            partial_restore=True,
        ))

    def place(old, new):
        arr = jax.numpy.asarray(new)
        if hasattr(old, "sharding"):
            return jax.device_put(arr, old.sharding)
        return arr

    weights = raw.get("ema_params") or raw.get("params")
    return state.replace(
        params=jax.tree.map(place, state.params, weights),
        model_state=jax.tree.map(
            place, state.model_state, raw.get("model_state") or {}
        ),
        step=place(state.step, raw.get("step", state.step)),
        ema_params=None,
    )


def read_weights(directory: str | Path, step: Optional[int] = None) -> dict:
    """Raw weights-only read to host: ``{"params", "model_state",
    "step"}``, preferring EMA weights when the checkpoint carries them
    (same policy as ``restore_eval_state``).  No target structure needed
    — the building block for cross-checkpoint tooling (averaging).

    Selects only the weight subtrees via a metadata-derived partial
    restore so the saved opt_state — potentially several times the param
    bytes — is never materialized."""
    directory = Path(directory).absolute()
    with _mgr(directory) as mgr:
        step = step if step is not None else mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
        saved = _saved_tree(directory, step)
        item = {
            k: jax.tree.map(
                lambda m: jax.ShapeDtypeStruct(m.shape, m.dtype), saved[k]
            )
            for k in _weight_keys(saved)
        }
        raw = mgr.restore(step, args=ocp.args.PyTreeRestore(
            item=item,
            restore_args=jax.tree.map(
                lambda _: ocp.RestoreArgs(restore_type=np.ndarray), item
            ),
            partial_restore=True,
        ))
    return {
        "params": raw.get("ema_params") or raw["params"],
        "model_state": raw.get("model_state") or {},
        "step": int(raw.get("step", step)),
    }


def average_checkpoints(
    sources,
    out_dir: str | Path,
    weights: Optional[list] = None,
) -> str:
    """Weight-space average of checkpoints (SWA / model-soup recipe —
    upstream's Catalyst world ships SWA; this is the TPU-native
    equivalent over orbax trees).

    ``sources``: iterable of ``"dir"`` or ``"dir:step"`` strings (or
    (dir, step) tuples).  Params AND model_state (BN statistics) average
    in fp32 — the standard cheap approximation; for BN-heavy models,
    re-estimate stats with a few forward passes afterwards if accuracy
    at the margin matters.  EMA weights are preferred per source.  The
    result is saved weights-only to ``out_dir`` at the max source step
    and restores through the normal eval path."""
    import numpy as np

    def parse(src):
        if isinstance(src, (tuple, list)):
            return str(src[0]), (None if len(src) < 2 else int(src[1]))
        s = str(src)
        # a trailing :<int> selects the step; plain paths pass through
        # (Windows drive letters are not int-parseable, so this is safe)
        if ":" in s:
            head, _, tail = s.rpartition(":")
            if tail.isdigit():
                return head, int(tail)
        return s, None

    parsed = [parse(s) for s in sources]
    if len(parsed) < 2:
        raise ValueError(f"averaging needs >= 2 checkpoints, got {len(parsed)}")
    if weights is None:
        weights = [1.0 / len(parsed)] * len(parsed)
    if len(weights) != len(parsed):
        raise ValueError(
            f"{len(weights)} weights for {len(parsed)} checkpoints"
        )
    total = float(sum(weights))
    if total <= 0:
        raise ValueError("weights must sum to a positive value")
    weights = [float(w) / total for w in weights]

    acc = None
    first_dtypes = None
    max_step = 0
    for (d, step), w in zip(parsed, weights):
        src = read_weights(d, step)
        max_step = max(max_step, src["step"])
        tree = {"params": src["params"], "model_state": src["model_state"]}

        def add(a, b, w=w):
            b32 = np.asarray(b, np.float64) * w
            return b32 if a is None else a + b32

        if acc is None:
            acc = jax.tree.map(lambda x: add(None, x), tree)
            ref_struct = jax.tree.structure(tree)
            first_dtypes = jax.tree.map(lambda x: jax.numpy.asarray(x).dtype,
                                        tree)
        else:
            if jax.tree.structure(tree) != ref_struct:
                raise ValueError(
                    f"checkpoint {d} has a different parameter structure"
                )
            acc = jax.tree.map(add, acc, tree)

    def cast_back(avg, dt):
        return jax.numpy.asarray(avg).astype(dt)

    out_tree = {
        "params": jax.tree.map(
            cast_back, acc["params"], first_dtypes["params"]
        ),
        "model_state": jax.tree.map(
            cast_back, acc["model_state"], first_dtypes["model_state"]
        ),
        "step": max_step,
    }
    return save_checkpoint(out_dir, out_tree, step=max_step)


def restore_checkpoint(
    directory: str | Path, target: Any, step: Optional[int] = None
) -> Any:
    """Restore into the structure of ``target`` (shapes/shardings from it).

    EMA tolerance: a TrainState's ``ema_params`` presence depends on the
    restoring task's own config, and downstream valid/infer tasks don't
    know whether the train task tracked EMA.  If the on-disk tree and the
    target disagree on ``ema_params``, the target is adapted:

    - saved WITH ema, target without → restore the EMA too (eval then
      runs on the EMA weights, which is the feature's whole point);
    - saved WITHOUT ema, target with → restore without, then seed the
      EMA from the restored params so tracking starts fresh.
    """
    directory = Path(directory).absolute()
    with _mgr(directory) as mgr:
        step = step if step is not None else mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
        try:
            return mgr.restore(step, args=ocp.args.StandardRestore(target))
        except ValueError as orig:
            # possibly an ema_params presence mismatch — retry with the
            # opposite interpretation (orbax's item_metadata is not
            # reliable across versions, so probe rather than inspect);
            # if the retry fails too, the mismatch was something else:
            # surface the ORIGINAL error, not the retry's
            try:
                if getattr(target, "ema_params", None) is not None:
                    # saved without ema, target tracks it: seed from params
                    restored = mgr.restore(
                        step,
                        args=ocp.args.StandardRestore(
                            target.replace(ema_params=None)
                        ),
                    )
                    return restored.replace(
                        ema_params=jax.tree.map(lambda p: p, restored.params)
                    )
                if hasattr(target, "ema_params") and hasattr(target, "params"):
                    # saved WITH ema, target doesn't track it: the EMA
                    # weights BECOME the params (they're the better weights
                    # and nothing would keep updating a dangling EMA copy)
                    adapted = target.replace(
                        ema_params=jax.tree.map(lambda p: p, target.params)
                    )
                    restored = mgr.restore(
                        step, args=ocp.args.StandardRestore(adapted)
                    )
                    return restored.replace(
                        params=restored.ema_params, ema_params=None
                    )
            except ValueError:
                pass
            raise orig
