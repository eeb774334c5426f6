"""Checkpoint / resume (orbax), with verified saves and last-good fallback.

The reference *parses* ``--resume <epoch> --checkpoint <dir> --interval <n>``
but never wires them: ``start_epoch = 0`` is hardcoded in all three trainers
and no save call exists (``resnet/colossal/colossal_train.py:40-42,163``,
SURVEY.md §5 "Checkpoint / resume"). Here the surface is functional: the full
train state — params, BatchNorm stats, optimizer state (including ZeRO
shards: orbax saves/restores respecting each array's sharding), dynamic
loss-scale state, step counter — plus the epoch index round-trips through
orbax.

Resilience round (docs/RESILIENCE.md): every save is *verified* — a
per-file/per-leaf checksum manifest plus an atomic ``COMMITTED`` marker
written last (``resilience/verify.py``) — and every restore path is
corruption-aware. A torn, uncommitted, or checksum-failing save raises
the typed :class:`~distributed_training_tpu.resilience.errors.
CheckpointCorruptError` (naming the directory and the remedy) instead of
an opaque orbax crash; :func:`latest_valid_epoch` scans newest→oldest
past bad saves (quarantining them to ``epoch_N.corrupt``) so
``auto_resume`` falls back to the newest *good* checkpoint, and
:func:`prune_checkpoints` never deletes the last verified one. Orbax
writes run under the deterministic :class:`~distributed_training_tpu.
resilience.retry.RetryPolicy` so a transient filesystem fault costs a
bounded retry, not the save.
"""

from __future__ import annotations

import os
import re
import warnings
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp
from flax import serialization

from distributed_training_tpu.resilience import verify as verify_lib
from distributed_training_tpu.resilience.errors import CheckpointCorruptError
from distributed_training_tpu.resilience.retry import RetryPolicy

# Transient-I/O retry for the orbax write itself. OSError only: a
# structural error (tree mismatch) must surface on the first attempt.
_CKPT_IO_RETRY = RetryPolicy(max_attempts=3, base_delay_s=0.1)

# ResNet blocks were renamed from Flax auto-names ("BasicBlock_3",
# "BottleneckBlock_0", remat-prefixed "CheckpointBasicBlock_1") to explicit
# "stage{i}_block{j}" names (models/resnet.py). Checkpoints saved before the
# rename are migrated on restore: auto-names number blocks sequentially in
# creation order, which is exactly "stage{i}_block{j}" sorted by (i, j).
_LEGACY_BLOCK_RE = re.compile(
    r"^(?:Checkpoint)?(?:BasicBlock|BottleneckBlock)_(\d+)$")
_NEW_BLOCK_RE = re.compile(r"^stage(\d+)_block(\d+)$")


def _epoch_dir(directory: str, epoch: int) -> str:
    return os.path.join(os.path.abspath(directory), f"epoch_{epoch}")


def save_checkpoint(directory: str, epoch: int, state: Any,
                    next_epoch: int | None = None,
                    epoch_step: int = 0,
                    layout: dict[str, int] | None = None) -> str:
    """Save the train state tagged ``epoch``; returns the checkpoint path.

    ``next_epoch`` is the epoch a resume should start at — ``epoch + 1``
    for the normal end-of-epoch save, or ``epoch`` itself for a preemption
    save taken *mid*-epoch. ``epoch_step`` records how many effective
    batches of that epoch were already consumed, so a resume skips exactly
    that prefix of the epoch's deterministic shuffle instead of re-training
    it (step-accurate resume; see ``runtime/preemption.py``).

    ``layout`` records storage-layout parameters the arrays' SHAPES cannot
    encode — e.g. the circular pipeline's layer permutation (a function of
    pipe_size × virtual_stages): a resume into a different layout would
    load shape-identical but silently permuted weights, so restore
    validates it (see :func:`restore_checkpoint`).

    Every save is *verified*: after the orbax write completes, checksum
    manifests and then an atomic ``COMMITTED`` marker are written — the
    marker last, so any earlier crash leaves a save that
    ``resilience/verify.py::verify_checkpoint`` classifies as
    uncommitted without reading array data. Single-process saves write
    one ``MANIFEST.json`` over every file plus per-leaf content
    checksums; multihost saves write per-process ``MANIFEST.<p>.json``
    files (each process hashes only its own orbax artifacts — nobody
    touches a peer's possibly-in-flight bytes) with the master
    committing last, after all peer manifests are visible.
    """
    path = _epoch_dir(directory, epoch)
    meta = {"epoch": np.int32(epoch),
            "next_epoch": np.int32(
                epoch + 1 if next_epoch is None else next_epoch),
            "epoch_step": np.int32(epoch_step)}
    for k, v in (layout or {}).items():
        meta[f"layout_{k}"] = np.int32(v)
    payload = {"state": serialization.to_state_dict(state), "meta": meta}
    ckptr = ocp.PyTreeCheckpointer()
    _CKPT_IO_RETRY.call(ckptr.save, path, payload, force=True)
    if jax.process_count() == 1:
        # Manifest + atomic COMMITTED marker, leaf checksums included
        # (host-materializable arrays only hold single-process).
        verify_lib.write_manifest(
            path, leaves=verify_lib.leaf_checksums(payload))
    else:
        # Multihost (round-9 gap closed): each process manifests ONLY
        # the files it owns — its orbax ocdbt.process_<p> artifacts,
        # plus the shared metadata on process 0 — so no process ever
        # hashes a peer's possibly-still-flushing bytes; the master
        # writes COMMITTED last, after every peer's manifest is
        # visible. Leaf checksums stay single-process-only (a host
        # cannot materialize peers' shards).
        verify_lib.write_manifest(
            path, process_index=jax.process_index(),
            process_count=jax.process_count())
    return path


def _rename_keys(tree: Any, mapping: dict[str, str]) -> Any:
    if isinstance(tree, dict):
        return {mapping.get(k, k): _rename_keys(v, mapping)
                for k, v in tree.items()}
    return tree


def _leaf_shapes(tree: Any, prefix: tuple = ()) -> dict[tuple, tuple]:
    """{path: shape} over a nested dict whose leaves carry ``.shape``
    (works for both arrays and orbax ArrayMetadata)."""
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_leaf_shapes(v, prefix + (k,)))
        return out
    return {prefix: tuple(getattr(tree, "shape", ()) or ())}


def _legacy_vit_rename(saved_state: Any, new_state: dict) -> dict[str, str]:
    """old-name → new-name map for pre-round-4 ViT saves (empty otherwise).

    Round 4 named ViT's submodules for the TP rule table
    (``models/vit.py``): flax auto names became ``attn``/``fc1``/``fc2``.
    Detected structurally: the template's encoder blocks carry ``attn``
    while the save carries the auto name. The map is applied at every tree
    level by ``_rename_keys``; within a ViT state the auto names are
    unambiguous (the only Dense_0/Dense_1 live under MlpBlock_0).
    """
    saved_params = (saved_state or {}).get("params")
    new_params = new_state.get("params")
    if not isinstance(saved_params, dict) or not isinstance(new_params, dict):
        return {}
    enc_new = new_params.get("encoder_0")
    enc_old = saved_params.get("encoder_0")
    if not (isinstance(enc_new, dict) and isinstance(enc_old, dict)):
        return {}
    if "attn" not in enc_new or "attn" in enc_old:
        return {}
    mapping = {"Dense_0": "fc1", "Dense_1": "fc2"}
    for legacy in ("MultiHeadDotProductAttention_0", "RingSelfAttention_0"):
        if legacy in enc_old:
            mapping[legacy] = "attn"
    return mapping


def _legacy_block_rename(saved_state: Any, new_state: dict) -> dict[str, str]:
    """old-name → new-name map for pre-rename ResNet checkpoints (empty if
    the save already uses explicit names or the shapes don't line up).

    Per-block leaf shapes are compared (saved metadata vs template arrays),
    so a genuinely incompatible checkpoint — e.g. a legacy resnet34 save
    restored into a resnet50 template with the same block *count* — is not
    migrated and instead surfaces the plain structural mismatch error.
    """
    saved_params = (saved_state or {}).get("params")
    new_params = new_state.get("params")
    if not isinstance(saved_params, dict) or not isinstance(new_params, dict):
        return {}
    legacy = sorted(
        (k for k in saved_params if _LEGACY_BLOCK_RE.match(k)),
        key=lambda k: int(_LEGACY_BLOCK_RE.match(k).group(1)))
    new = sorted(
        (k for k in new_params if _NEW_BLOCK_RE.match(k)),
        key=lambda k: tuple(map(int, _NEW_BLOCK_RE.match(k).groups())))
    if not legacy or len(legacy) != len(new):
        return {}
    for o, n in zip(legacy, new):
        if _leaf_shapes(saved_params[o]) != _leaf_shapes(new_params[n]):
            return {}
    return dict(zip(legacy, new))


def restore_checkpoint(directory: str, epoch: int, state: Any,
                       layout: dict[str, int] | None = None,
                       ) -> tuple[Any, int, int]:
    """Restore the checkpoint tagged ``epoch``; returns
    ``(state, start_epoch, start_step)``.

    ``start_epoch`` comes from the checkpoint's ``next_epoch`` meta
    (normally ``epoch + 1`` — the Colossal ``--resume <epoch>`` semantics);
    ``start_step`` is the number of ``start_epoch``'s batches already
    trained (nonzero only for mid-epoch preemption saves — the resume
    skips that prefix of the epoch's deterministic shuffle).

    Format differences are detected *explicitly* from the on-disk tree
    structure (``metadata()``, no array reads) rather than by retrying on
    exceptions, so a genuine restore failure surfaces its real cause:

    - pre-``next_epoch`` saves carry only ``{epoch}`` → old ``epoch + 1``
      resume semantics; pre-``epoch_step`` saves resume at step 0;
    - pre-rename ResNet saves use Flax auto block names → keys are migrated
      to the explicit ``stage{i}_block{j}`` names everywhere in the state
      (params, batch_stats, and the param-shaped optimizer moments).
    """
    path = _epoch_dir(directory, epoch)
    if not os.path.isdir(path):
        raise FileNotFoundError(f"no checkpoint at {path}")
    # Validity gate BEFORE orbax touches the tree: a partial/empty/torn
    # save used to surface as a raw orbax exception deep in metadata or
    # array deserialization; now it is the typed CheckpointCorruptError
    # naming the directory and the remedy (resilience/verify.py).
    verify_lib.verify_checkpoint(path)
    ckptr = ocp.PyTreeCheckpointer()
    saved = ckptr.metadata(path).item_metadata.tree or {}
    state_template = serialization.to_state_dict(state)
    rename = _legacy_block_rename(saved.get("state"), state_template)
    rename.update(_legacy_vit_rename(saved.get("state"), state_template))
    if rename:
        # Present orbax a template keyed by the on-disk (legacy) names while
        # keeping the template's array leaves (shardings drive the restore).
        state_template = _rename_keys(
            state_template, {n: o for o, n in rename.items()})
    saved_meta = saved.get("meta", {})
    meta_template = {"epoch": np.int32(0)}
    for key in saved_meta:
        if key in ("next_epoch", "epoch_step") or key.startswith("layout_"):
            meta_template[key] = np.int32(0)
    # Meta first (a handful of scalars, partial restore): the layout guard
    # must refuse BEFORE the potentially-multi-GB state read. Identical
    # shapes can hide a permuted layout (the circular pipeline's layer
    # stacking); symmetric compare with default 1/identity on both sides,
    # so legacy saves without the key count as identity and a saved
    # non-identity key the caller did not declare still refuses.
    meta = ckptr.restore(
        path, item={"meta": meta_template}, partial_restore=True)["meta"]
    saved_layout = {k[len("layout_"):]: int(v) for k, v in meta.items()
                    if k.startswith("layout_")}
    want_layout = {k: int(v) for k, v in (layout or {}).items()}
    for k in sorted(set(saved_layout) | set(want_layout)):
        have, want = saved_layout.get(k, 1), want_layout.get(k, 1)
        if have != want:
            raise ValueError(
                f"checkpoint at {path} was saved with layout {k}={have}, "
                f"but this run expects {k}={want}; the stacked arrays are "
                f"shape-identical but PERMUTED — resume with the saving "
                f"configuration instead of loading silently wrong weights")
    # Full STRICT restore (no partial_restore: a tree mismatch must raise,
    # not silently hand back template values for missing leaves).
    restored = ckptr.restore(
        path, item={"state": state_template, "meta": meta_template})
    next_epoch = (int(meta["next_epoch"]) if "next_epoch" in meta
                  else int(meta["epoch"]) + 1)
    start_step = int(meta.get("epoch_step", 0))
    restored_state = (_rename_keys(restored["state"], rename)
                      if rename else restored["state"])
    new_state = serialization.from_state_dict(state, restored_state)
    return new_state, next_epoch, start_step


def resolve_resume(ckpt_cfg) -> int:
    """Resume epoch for a :class:`CheckpointConfig`: an explicit
    ``resume >= 0`` wins (restore then raises the typed
    ``CheckpointCorruptError`` if that save is bad — the user named it,
    so silence would be lying); else ``auto_resume`` finds the newest
    *verified* save, skipping and quarantining torn/uncommitted ones
    (the preemption-restart pairing, ``runtime/preemption.py``);
    -1 = fresh.
    """
    if ckpt_cfg.resume >= 0:
        return ckpt_cfg.resume
    if ckpt_cfg.auto_resume:
        latest = latest_valid_epoch(ckpt_cfg.directory)
        if latest is not None:
            return latest
    return -1


def _epoch_list(directory: str) -> list[int]:
    if not os.path.isdir(directory):
        return []
    return sorted(
        int(d.split("_", 1)[1])
        for d in os.listdir(directory)
        if d.startswith("epoch_") and d.split("_", 1)[1].isdigit()
    )


def latest_epoch(directory: str) -> int | None:
    """Highest epoch with a saved checkpoint (validity NOT checked — use
    :func:`latest_valid_epoch` for resume decisions), or None."""
    epochs = _epoch_list(os.path.abspath(directory))
    return max(epochs) if epochs else None


def latest_valid_epoch(directory: str, *,
                       quarantine: bool = True) -> int | None:
    """Newest epoch whose save passes verification, or None.

    Scans newest→oldest; an uncommitted / torn / checksum-failing dir is
    skipped and (when ``quarantine``, process 0 only) renamed to
    ``epoch_N.corrupt`` so later scans stop re-hashing it while the
    bytes stay available for forensics. This is the fallback behind
    ``auto_resume``: a preemption that tore the newest save silently
    costs one epoch of progress instead of the run.
    """
    directory = os.path.abspath(directory)
    for e in reversed(_epoch_list(directory)):
        path = _epoch_dir(directory, e)
        try:
            verify_lib.verify_checkpoint(path)
            return e
        except CheckpointCorruptError as err:
            if quarantine and jax.process_index() == 0:
                dst = verify_lib.quarantine_checkpoint(path)
                warnings.warn(
                    f"skipping corrupt checkpoint (quarantined to {dst}): "
                    f"{err}", stacklevel=2)
            else:
                warnings.warn(f"skipping corrupt checkpoint: {err}",
                              stacklevel=2)
        except OSError as err:
            # A dir vanishing mid-verify (another process's quarantine
            # rename, a concurrent prune) or a transient read fault must
            # skip this candidate, not kill the very scan that exists to
            # survive bad saves. No quarantine: the dir may be gone or
            # healthy-but-unreadable right now.
            warnings.warn(
                f"skipping unreadable checkpoint {path}: {err}",
                stacklevel=2)
    return None


def prune_checkpoints(directory: str, keep: int) -> None:
    """Retain the ``keep`` newest epoch checkpoints (process 0 only) —
    and NEVER the last verified one: when every newer save is torn or
    uncommitted, deleting the newest *good* save by age would leave the
    run nothing to fall back to."""
    if jax.process_index() != 0:
        return
    directory = os.path.abspath(directory)
    epochs = _epoch_list(directory)
    if not epochs or keep <= 0:
        return
    victims = epochs[:-keep]
    if not victims:
        return
    # A victim needs protection only when NO surviving (kept) epoch
    # verifies — otherwise a newer verified save outlives the sweep by
    # construction. The common case therefore verifies at most the
    # newest survivor and never re-hashes the victims. Quarantining here
    # would be a surprising side effect of a retention sweep, so the
    # scan is verify-only.
    protected = None
    if not any(verify_lib.checkpoint_is_valid(_epoch_dir(directory, e))
               for e in reversed(epochs[-keep:])):
        protected = next(
            (e for e in reversed(victims)
             if verify_lib.checkpoint_is_valid(_epoch_dir(directory, e))),
            None)
    import shutil

    for e in victims:
        if e == protected:
            continue
        shutil.rmtree(_epoch_dir(directory, e), ignore_errors=True)
