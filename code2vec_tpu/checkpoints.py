"""Checkpoint / resume / release via orbax.

Reference parity (tensorflow_model.py:370-377, keras_model.py:230-296,
SURVEY.md §5 'Checkpoint / resume'):

- per-epoch saves, ``max_to_keep=10`` (reference config.py:57);
- the vocab sidecar ``dictionaries.bin`` lives next to the checkpoints
  (model_base.py:102-109) — written by the caller;
- **release** = params-only strip (the reference re-saves without optimizer
  state for a ~3× smaller artifact, tensorflow_model.py:132-136,
  README.md:212-219): params go under ``<path>__only-weights``;
- full state (params + Adam moments + step + epoch) goes under
  ``<path>__entire-model`` (the Keras backend's naming, config.py:196-202);
- the epoch number is stored explicitly in the checkpoint metadata — the
  reference recovered it by parsing checkpoint filenames and left a TODO
  for doing it properly (keras_model.py:274, 285-287).

Orbax writes sharded arrays natively: on a mesh, each host saves its own
shards (async-capable), and restore re-shards to the current mesh.
"""
from __future__ import annotations

import inspect
import json
import logging
import os
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

# package logger: 'code2vec_tpu.checkpoints' — propagates to the
# 'code2vec_tpu' root logger Config.get_logger configures
logger = logging.getLogger(__name__)

from code2vec_tpu.config import Config
from code2vec_tpu.resilience import faults


class CheckpointLayoutError(ValueError):
    """Permanent, store-wide restore failure (pre-canonical layout or a
    cross-framework training resume): every artifact under the store
    shares the cause, so the corruption fallback must re-raise instead
    of quarantining its way through good data."""

# orbax version split for the params-only partial restore: newer orbax
# has PyTreeRestore(partial_restore=True) dispatched through the
# manager's handler registry; 0.7.x (this image's toolchain) has neither
# — there the equivalent is a standalone PyTreeCheckpointHandler with
# the transforms={} mechanism, and registering a SECOND handler instance
# for the same item corrupts saves (each instance finalizes its own tmp
# dir onto the item path — reproduced on 0.7.0).
_PYTREE_PARTIAL_RESTORE = 'partial_restore' in inspect.signature(
    ocp.args.PyTreeRestore.__init__).parameters


class RestoredTraining(NamedTuple):
    params: Any
    opt_state: Any
    step: int
    epoch: int


# --------------------------------------------------------------------------
# Target-table row adaptation (ADVICE r3): the target table's padded row
# count folds in the fused-CE vocab tile and the mesh model-axis size
# (backends.target_row_alignment), so a checkpoint written under one
# topology/fused-CE setting allocates a different row count than a resume
# under another. The extra rows are pure padding — masked out of the
# softmax by num_valid_targets and receiving zero gradient (hence zero Adam
# moments) — so restore can pad with zeros or slice them off exactly. The
# adapted leaves are identified by keypath name: 'target_embedding' names
# the table in the canonical params dict, the optax moment NamedTuples, and
# the flax param dict alike.

_TARGET_ROWS_KEY = 'target_vocab_rows'
_TARGET_LEAF_NAME = 'target_embedding'


def _is_target_path(path) -> bool:
    last = path[-1]
    name = getattr(last, 'name', None)
    if name is None:
        name = getattr(last, 'key', None)
    return name == _TARGET_LEAF_NAME


def _with_target_rows(abstract_tree, rows: int):
    """Abstract tree with target-table leaves' leading dim set to ``rows``
    (the STORED allocation), keeping dtype and current-mesh sharding."""
    def fix(path, leaf):
        if not _is_target_path(path) or leaf.shape[0] == rows:
            return leaf
        return jax.ShapeDtypeStruct((rows,) + tuple(leaf.shape[1:]),
                                    leaf.dtype,
                                    sharding=getattr(leaf, 'sharding', None))
    return jax.tree_util.tree_map_with_path(fix, abstract_tree)


def _resize_target_rows(tree, abstract_tree, rows: int):
    """Pad (zeros) or slice restored target-table leaves to ``rows`` (the
    CURRENT allocation), re-laid-out to the abstract leaf's sharding.
    Slicing is exact because the current allocation always covers the
    valid vocabulary rows; rows beyond them are masked padding.

    The resize runs under ``jax.jit`` with an explicit ``out_shardings``:
    on a multi-process mesh the restored leaves are row-sharded and NOT
    fully addressable, where eager slicing / ``device_put`` raise — jit
    of a computation over global arrays is the legal spelling (advisor
    r4, medium)."""
    def fix(path, leaf, abstract_leaf):
        if not _is_target_path(path) or leaf.shape[0] == rows:
            return leaf
        if leaf.shape[0] > rows:
            resize = lambda x: jax.lax.slice_in_dim(x, 0, rows, axis=0)
        else:
            pad = [(0, rows - leaf.shape[0])] + [(0, 0)] * (leaf.ndim - 1)
            resize = lambda x: jax.numpy.pad(x, pad)
        sharding = getattr(abstract_leaf, 'sharding', None)
        if sharding is None or not isinstance(leaf, jax.Array):
            return resize(leaf)
        return jax.jit(resize, out_shardings=sharding)(leaf)
    return jax.tree_util.tree_map_with_path(fix, tree, abstract_tree)


def _target_rows_from_metadata(tree_meta) -> Optional[int]:
    """Target-table row count read from orbax's OWN saved array metadata,
    i.e. from the artifact being restored. The shared ``.meta.json``
    sidecar records only the NEWEST writer's row count, so after e.g. a
    ``--release`` under a reshaped config it lies about older epoch
    checkpoints (advisor r4); the per-artifact metadata cannot."""
    tree = getattr(tree_meta, 'tree', tree_meta)
    found = []

    def walk(node):
        if isinstance(node, dict):
            for key, value in node.items():
                if key == _TARGET_LEAF_NAME:
                    shape = getattr(value, 'shape', None)
                    if shape:
                        found.append(int(shape[0]))
                else:
                    walk(value)
        elif isinstance(node, (list, tuple)):
            for value in node:
                walk(value)

    walk(tree)
    return found[0] if found else None


# Adam moment subtrees subject to storage-dtype adaptation on restore:
# ADAM_MU_DTYPE's default flipped 'float32' -> 'bfloat16' (2026-07-31) and
# ADAM_NU_DTYPE is A/B-gated the same way, so a resume under either
# setting of a checkpoint written under the other must adapt instead of
# failing on a dtype mismatch. Field names follow optax.ScaleByAdamState
# (training/adam_dtypes.py keeps them for exactly this reason).
_MOMENT_FIELDS = ('mu', 'nu')


def _path_has_field(path, field: str) -> bool:
    for entry in path:
        name = getattr(entry, 'name', None)
        if name is None:
            name = getattr(entry, 'key', None)
        if name == field:
            return True
    return False


def _moment_dtype_from_metadata(tree_meta, field: str):
    """Storage dtype of the Adam moment subtree named ``field`` in the
    artifact being restored, from orbax's own saved array metadata. None
    when the artifact has no such subtree or its dtypes are
    non-uniform."""
    tree = getattr(tree_meta, 'tree', tree_meta)
    dtypes = set()

    def walk(node, under):
        if isinstance(node, dict):
            for key, value in node.items():
                walk(value, under or key == field)
        elif isinstance(node, (list, tuple)):
            for value in node:
                walk(value, under)
        elif under:
            dt = getattr(node, 'dtype', None)
            if dt is not None and jax.numpy.issubdtype(dt,
                                                       jax.numpy.floating):
                dtypes.add(np.dtype(dt))

    walk(tree, False)
    return dtypes.pop() if len(dtypes) == 1 else None


def _moment_dtype_of(abstract_tree, field: str):
    """The (uniform) floating dtype of the ``field`` moment leaves in an
    abstract optimizer-state tree, or None."""
    dtypes = set()

    def visit(path, leaf):
        if _path_has_field(path, field) and jax.numpy.issubdtype(
                leaf.dtype, jax.numpy.floating):
            dtypes.add(np.dtype(leaf.dtype))
        return leaf

    jax.tree_util.tree_map_with_path(visit, abstract_tree)
    return dtypes.pop() if len(dtypes) == 1 else None


def _leaf_paths(tree) -> set:
    """The key paths of a tree's array leaves, as tuples of strings.
    Reads an abstract optimizer state and orbax's saved metadata of one
    alike: there a NamedTuple is a dict by field and a tuple a list."""
    tree = getattr(tree, 'tree', tree)
    paths = set()

    def walk(node, path):
        if isinstance(node, dict):
            for key, value in node.items():
                walk(value, path + (str(key),))
        elif isinstance(node, (list, tuple)):
            fields = getattr(node, '_fields', range(len(node)))
            for key, value in zip(fields, node):
                walk(value, path + (str(key),))
        elif node is not None:
            paths.add(path)

    walk(tree, ())
    return paths


def _with_moment_dtype(abstract_tree, dtype, field: str):
    """Abstract tree with the ``field`` moment's floating leaves set to
    ``dtype`` (the STORED moment dtype), keeping shape and sharding — the
    restore target must match what is on disk; the cast back to the
    configured dtype happens after restore (`_cast_moment`)."""
    def fix(path, leaf):
        if not _path_has_field(path, field):
            return leaf
        if not jax.numpy.issubdtype(leaf.dtype, jax.numpy.floating):
            return leaf
        if np.dtype(leaf.dtype) == np.dtype(dtype):
            return leaf
        return jax.ShapeDtypeStruct(leaf.shape, dtype,
                                    sharding=getattr(leaf, 'sharding',
                                                     None))
    return jax.tree_util.tree_map_with_path(fix, abstract_tree)


def _cast_moment(tree, abstract_tree, field: str):
    """Cast restored ``field`` moment leaves to the configured dtype from
    the abstract target (fp32 -> bf16 rounds the way the bf16-moment
    update does every step; bf16 -> fp32 is exact). Runs under ``jax.jit``
    with explicit ``out_shardings`` — the legal spelling on
    non-fully-addressable multi-process arrays (same rationale as
    `_resize_target_rows`)."""
    def fix(path, leaf, abstract_leaf):
        if not _path_has_field(path, field):
            return leaf
        if not hasattr(leaf, 'dtype') or not jax.numpy.issubdtype(
                leaf.dtype, jax.numpy.floating):
            return leaf
        want = np.dtype(abstract_leaf.dtype)
        if np.dtype(leaf.dtype) == want:
            return leaf
        cast = lambda x: x.astype(want)
        sharding = getattr(abstract_leaf, 'sharding', None)
        if sharding is None or not isinstance(leaf, jax.Array):
            return cast(leaf)
        return jax.jit(cast, out_shardings=sharding)(leaf)
    return jax.tree_util.tree_map_with_path(fix, tree, abstract_tree)


class CheckpointStore:
    """Orbax-backed store for one model path prefix."""

    def __init__(self, model_path: str, max_to_keep: int = 10,
                 metadata: Optional[Dict[str, Any]] = None,
                 snapshot_max_to_keep: int = 2):
        self.model_path = model_path
        self.entire_dir = os.path.abspath(
            Config.get_entire_model_path(model_path))
        self.weights_dir = os.path.abspath(
            Config.get_model_weights_path(model_path))
        # step-interval snapshots (preemption insurance) live in their own
        # manager with a small retention window, so frequent interval saves
        # can never evict the epoch-boundary history max_to_keep promises
        self.snapshot_dir = os.path.abspath(
            Config.get_step_snapshots_path(model_path))
        self._manager: Optional[ocp.CheckpointManager] = None
        self._snapshot_manager: Optional[ocp.CheckpointManager] = None
        self.max_to_keep = max_to_keep
        self.snapshot_max_to_keep = snapshot_max_to_keep
        # shape-determining settings (e.g. PARAM_ROW_ALIGNMENT): written at
        # save, verified before restore so a mismatch is a clear config
        # error instead of an opaque orbax shape mismatch
        self.metadata = metadata or {}
        self.meta_path = os.path.abspath(model_path) + '.meta.json'

    #: stamped into every meta file; absence marks a checkpoint written
    #: before the canonical flat {name: array} params layout
    _LAYOUT = 'canonical-v1'

    def _write_metadata(self) -> None:
        if not self.metadata:
            return
        to_write = dict(self.metadata, checkpoint_layout=self._LAYOUT)
        stored = self._stored_metadata()
        for key in self._PRESERVE_ON_WRITE:
            # the original writer wins: e.g. --release under another
            # framework must not relabel the training checkpoint's
            # framework, or the resume diagnostic below lies
            if key in stored:
                to_write[key] = stored[key]
        with open(self.meta_path, 'w') as f:
            json.dump(to_write, f)

    # identity keys where the ORIGINAL writer wins on re-save
    _PRESERVE_ON_WRITE = frozenset({'framework'})
    # metadata keys whose mismatch does not reject a restore: 'framework'
    # is informational for params-only loads (the canonical checkpoint
    # layout is backend-agnostic); target_vocab_rows differences are
    # ADAPTED on restore (pad/slice of masked padding rows), so fused-CE
    # checkpoints stay loadable across mesh reshapes. Unlike 'framework',
    # target_vocab_rows tracks the NEWEST save; since it can therefore lie
    # about OLDER artifacts sharing the sidecar, restores read the actual
    # row count per artifact from orbax's array metadata and use the
    # sidecar only as a fallback (_artifact_target_rows).
    _NON_STRICT_KEYS = frozenset({'framework', _TARGET_ROWS_KEY})

    def verify_metadata(self) -> None:
        if not self.metadata or not os.path.isfile(self.meta_path):
            return
        stored = self._stored_metadata()
        for key, value in self.metadata.items():
            if key in self._NON_STRICT_KEYS:
                continue
            if key in stored and stored[key] != value:
                raise ValueError(
                    'Checkpoint at `%s` was saved with %s=%r but the current '
                    'config has %s=%r; these settings determine parameter '
                    'shapes and must match.' % (self.model_path, key,
                                                stored[key], key, value))

    def _stored_metadata(self) -> Dict[str, Any]:
        if not os.path.isfile(self.meta_path):
            return {}
        with open(self.meta_path, 'r') as f:
            return json.load(f)

    def _stored_target_rows(self) -> Optional[int]:
        """The target-table row count the checkpoint was SAVED with, when
        recorded — restore targets must use it, then adapt to the current
        allocation (see the module-level row-adaptation note)."""
        rows = self._stored_metadata().get(_TARGET_ROWS_KEY)
        return int(rows) if rows is not None else None

    def _artifact_target_rows(self, read_metadata) -> Optional[int]:
        """Saved row count for ONE artifact: orbax's own array metadata
        first (exact per artifact), the shared sidecar as fallback for
        artifacts written before metadata was readable.  The fallback is
        LOUD: the sidecar tracks only the newest writer, so trusting it
        for an older artifact can rebuild the opaque shape mismatch this
        path exists to remove."""
        try:
            rows = _target_rows_from_metadata(read_metadata())
        except Exception as exc:
            rows = None
            fallback_reason = repr(exc)
        else:
            fallback_reason = 'no target-table leaf in artifact metadata'
        if rows is not None:
            return rows
        sidecar = self._stored_target_rows()
        if sidecar is not None:
            logger.warning(
                'checkpoint %s: per-artifact row metadata unavailable '
                '(%s); falling back to the shared sidecar value %d, which '
                'may be wrong for older artifacts', self.model_path,
                fallback_reason, sidecar)
        return sidecar

    # ------------------------------------------------------------- manager
    @staticmethod
    def _handler_registry():
        """A FRESH manager (a resuming process that never saved) cannot
        reconstruct item_metadata without knowing the handler — and the
        per-artifact row-count read depends on it.  Registering both the
        Standard handler (save / full restore / metadata) and the PyTree
        handler (the params-only partial_restore path) keeps every
        existing call pattern working."""
        from orbax.checkpoint import handlers
        registry = handlers.DefaultCheckpointHandlerRegistry()
        standard = ocp.StandardCheckpointHandler()
        registry.add('default', ocp.args.StandardSave, standard)
        registry.add('default', ocp.args.StandardRestore, standard)
        if _PYTREE_PARTIAL_RESTORE:
            # newer orbax routes the params-only partial restore through
            # this registration; on 0.7.x it goes through a standalone
            # handler instead (module comment) — and the extra handler
            # instance here would corrupt saves
            registry.add('default', ocp.args.PyTreeRestore,
                         ocp.PyTreeCheckpointHandler())
        return registry

    def manager(self) -> ocp.CheckpointManager:
        if self._manager is None:
            self._manager = ocp.CheckpointManager(
                self.entire_dir,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=self.max_to_keep, create=True),
                handler_registry=self._handler_registry())
        return self._manager

    def snapshot_manager(self) -> ocp.CheckpointManager:
        if self._snapshot_manager is None:
            self._snapshot_manager = ocp.CheckpointManager(
                self.snapshot_dir,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=self.snapshot_max_to_keep, create=True),
                handler_registry=self._handler_registry())
        return self._snapshot_manager

    def wait_until_finished(self) -> None:
        """Drain any in-flight async save on either manager WITHOUT
        closing it (preemption's final save must be durable inside the
        signal grace window; the divergence rewind reads the newest
        snapshot right after a possible interval save)."""
        if self._manager is not None:
            self._manager.wait_until_finished()
        if self._snapshot_manager is not None:
            self._snapshot_manager.wait_until_finished()

    def close(self) -> None:
        # exception-safe: a failure draining one manager must not abandon
        # the other's in-flight async save
        try:
            if self._manager is not None:
                self._manager.close()
        finally:
            self._manager = None
            try:
                if self._snapshot_manager is not None:
                    self._snapshot_manager.close()
            finally:
                self._snapshot_manager = None

    # ---------------------------------------------------------------- save
    def save_training(self, *, params, opt_state, step: int,
                      epoch: int, wait: bool = False,
                      snapshot: bool = False) -> bool:
        """Async by default: orbax copies device arrays to host
        synchronously (<1 train step of stall), then persists in the
        background while training continues (SURVEY.md §5's 'orbax async
        checkpointing'). ``close()`` and the next ``save_training`` drain
        any in-flight save.

        Checkpoints are keyed by the global *step*; ``epoch`` records the
        last fully completed epoch for resume.  ``snapshot=True`` routes
        step-interval saves (``SAVE_EVERY_N_STEPS``) to the separate
        short-retention snapshot manager."""
        state = {'params': params, 'opt_state': opt_state,
                 'step': np.asarray(step, np.int32),
                 'epoch': np.asarray(epoch, np.int32)}
        manager = self.snapshot_manager() if snapshot else self.manager()
        saved = manager.save(step, args=ocp.args.StandardSave(state))
        if saved is False:
            # orbax silently skips step <= latest_step: with rewind
            # hygiene (purge_steps_newer_than) this should not happen —
            # a skipped save the caller believes durable is lost work
            logger.warning(
                'checkpoint %s: orbax SKIPPED the save at step %d '
                '(a retained step with an equal or newer key exists) — '
                'this state was NOT persisted', self.model_path, step)
        if wait:
            manager.wait_until_finished()
        if snapshot and faults.maybe_fire('corrupt_snapshot'):
            # fault drill (ROBUSTNESS.md): finalize the async write, then
            # truncate the artifact — the exact on-disk state a disk-full
            # or killed writer leaves, which restore must fall back past
            manager.wait_until_finished()
            faults.corrupt_directory(
                os.path.join(str(manager.directory), str(step)))
        self._write_metadata()
        return saved is not False

    def save_release(self, params) -> None:
        """Params-only artifact (the reference's ``--release``)."""
        checkpointer = ocp.StandardCheckpointer()
        path = self.weights_dir
        if os.path.exists(path):
            import shutil
            shutil.rmtree(path)
        checkpointer.save(path, {'params': params})
        checkpointer.wait_until_finished()
        checkpointer.close()
        self._write_metadata()

    # ------------------------------------------------------------- restore
    @staticmethod
    def _disk_steps(directory: str) -> set:
        """Committed step directories on disk (orbax commits by rename,
        so in-flight tmp dirs carry a suffix and never match)."""
        try:
            return {int(name) for name in os.listdir(directory)
                    if name.isdigit()}
        except OSError:
            return set()

    def _restore_candidates(self) -> list:
        """Every retained (manager, step) across the epoch and snapshot
        managers, NEWEST step first — the corruption-fallback order.
        Keys are global steps (older checkpoints were keyed by epoch —
        restore handles either, the stored state carries both numbers).

        Cross-process freshness: an orbax manager caches its step list
        at open, so a step saved by ANOTHER process afterwards (a
        serving worker following a live trainer's store, a mesh worker
        asked to adopt a step the parent just wrote) would be invisible
        forever.  When the directory holds a committed step the cached
        list doesn't know, the managers are reopened to resync."""
        for directory, manager in (
                (self.entire_dir, self._manager),
                (self.snapshot_dir, self._snapshot_manager)):
            if manager is None or not os.path.isdir(directory):
                continue
            known = {int(step) for step in manager.all_steps()}
            if not self._disk_steps(directory) <= known:
                self.close()  # reopen lazily with the fresh step list
                break
        candidates = []
        if os.path.isdir(self.entire_dir):
            for step in self.manager().all_steps():
                candidates.append((self.manager(), int(step)))
        if os.path.isdir(self.snapshot_dir):
            for step in self.snapshot_manager().all_steps():
                candidates.append((self.snapshot_manager(), int(step)))
        return sorted(candidates, key=lambda c: c[1], reverse=True)

    def _newest(self) -> Optional[Tuple[ocp.CheckpointManager, int]]:
        """(manager, step) of the newest checkpoint across both stores."""
        candidates = self._restore_candidates()
        return candidates[0] if candidates else None

    def has_step(self, step: int) -> bool:
        """True when a retained checkpoint in either store holds
        ``step`` (preemption save verification)."""
        return any(s == step for _m, s in self._restore_candidates())

    def newest_step(self) -> Optional[int]:
        """Newest retained step across both stores, or None when the
        path holds no checkpoints (serving rollover polling —
        ``ServingEngine.follow_checkpoints``, SERVING.md)."""
        newest = self._newest()
        return newest[1] if newest else None

    def _quarantine(self, manager, step: int,
                    suffix: str = '.corrupt') -> None:
        """Move a step directory ASIDE (rename to ``<step><suffix>``) so
        neither retention nor the next restore trips over it again.
        Best-effort and reversible: a false positive (e.g. a transient
        read error) is recovered by renaming the directory back."""
        step_dir = os.path.join(str(manager.directory), str(step))
        try:
            if os.path.isdir(step_dir):
                # unique destination: a REPEAT rewind can quarantine the
                # same step number again (re-saved after the first
                # purge), and os.replace onto an existing non-empty dir
                # would fail, leaving the poisoned artifact in place
                dest = step_dir + suffix
                serial = 1
                while os.path.exists(dest):
                    serial += 1
                    dest = '%s%s.%d' % (step_dir, suffix, serial)
                os.replace(step_dir, dest)
                logger.warning(
                    'checkpoint %s: quarantined step %d to `%s`',
                    self.model_path, step, dest)
        except OSError as exc:
            logger.warning('checkpoint %s: could not quarantine step %d '
                           '(%s)', self.model_path, step, exc)

    def purge_steps_newer_than(self, step: int) -> None:
        """Quarantine every retained step NEWER than ``step``, across
        both stores (suffix ``.rewound``).  Divergence-rewind hygiene:
        artifacts saved inside the poisoned window (a) would shadow the
        rewound state as 'newest' for a crash-resume, and (b) hold their
        step keys, which makes orbax silently no-op any later re-save at
        or below them (``manager.save`` returns False for
        ``step <= latest_step``)."""
        for manager, retained in self._restore_candidates():
            if retained > step:
                self._quarantine(manager, retained, suffix='.rewound')
        # the managers' in-memory checkpoint lists still name the purged
        # steps; reopening on next use resyncs them with the directory
        self.close()

    def _raise_if_permanent(self, exc: Exception) -> None:
        """Re-raise a restore failure as a clear, store-wide error when
        the sidecar says it cannot be corruption: a pre-canonical layout
        or a cross-framework training resume affects EVERY retained step,
        so falling back to older artifacts cannot help."""
        stored = self._stored_metadata()
        if stored and stored.get('checkpoint_layout') != self._LAYOUT:
            raise CheckpointLayoutError(
                'Checkpoint at `%s` predates the canonical parameter '
                'layout (no checkpoint_layout marker); it cannot be '
                'restored by this version. Re-save it from the version '
                'that wrote it.' % self.model_path) from exc
        stored_fw = stored.get('framework') if stored else None
        current_fw = self.metadata.get('framework')
        if stored_fw and current_fw and stored_fw != current_fw:
            raise CheckpointLayoutError(
                'Cannot resume TRAINING from `%s` with framework=%r: '
                'the checkpoint was written by framework=%r and '
                'optimizer state is backend-specific. Params-only '
                'loads (evaluate / predict / --release) work across '
                'frameworks.' % (self.model_path, current_fw,
                                 stored_fw)) from exc

    def _raise_if_foreign_optimizer(self, exc: Exception, read_metadata,
                                    abstract_opt_state) -> None:
        """Re-raise a failed training restore as a clear, store-wide
        error when the artifact's optimizer state is not the tree this
        configuration builds (another framework's, or an optimizer this
        version no longer has): no older step can help, and orbax's own
        message is a tree diff."""
        try:
            stored = _leaf_paths(read_metadata())
        except Exception:
            return  # unreadable metadata: the restore's own error stands
        stored_opt = {path[1:] for path in stored
                      if path[:1] == ('opt_state',)}
        if stored_opt == _leaf_paths(abstract_opt_state):
            return
        self._raise_if_permanent(exc)
        raise CheckpointLayoutError(
            'Cannot resume TRAINING from `%s`: its optimizer state is '
            'not the Adam state this version builds (written by an '
            'optimizer this version does not have). Params-only loads '
            '(evaluate / predict / --release) work.'
            % self.model_path) from exc

    def restore_training(self, abstract_params, abstract_opt_state,
                         max_step: Optional[int] = None
                         ) -> Optional[RestoredTraining]:
        """Restore the newest RESTORABLE full training state (epoch
        checkpoint or step-interval snapshot), re-sharded to match the
        abstract target (shapes + shardings).  ``max_step`` excludes
        newer steps (the divergence guard passes its last KNOWN-FINITE
        step so it never rewinds into a snapshot saved after the
        divergence began).

        A step that fails to restore (partial/corrupt write: disk-full,
        preemption mid-finalize) is logged and skipped in favor of the
        next-older retained step — losing one save interval beats losing
        the run.  Quarantine (rename to ``<step>.corrupt``) is DEFERRED
        until some older step actually restores: a failure shared by
        every candidate is a config/environment problem, and renaming the
        whole history aside would destroy good data — that case raises
        with the newest failure instead."""
        candidates = self._restore_candidates()
        if max_step is not None:
            candidates = [c for c in candidates if c[1] <= max_step]
        if not candidates:
            return None
        self.verify_metadata()
        return self._restore_with_fallback(
            candidates,
            lambda manager, step: self._restore_training_at(
                manager, step, abstract_params, abstract_opt_state),
            what='restore')

    def _restore_with_fallback(self, candidates, attempt, what: str):
        """The shared corruption-fallback policy (restore_training and
        restore_params): try ``attempt(manager, step)`` newest first;
        store-wide failures (CheckpointLayoutError / sidecar-permanent)
        re-raise immediately; others fall back to the next older step.
        Quarantine of failed steps is DEFERRED until some step actually
        restores — when every candidate fails the error re-raises and
        nothing is renamed (a shared failure is a config/environment
        cause, not corruption)."""
        failed: list = []   # (manager, step, exc) awaiting quarantine
        for manager, step in candidates:
            try:
                restored = attempt(manager, step)
            except CheckpointLayoutError:
                raise
            except Exception as exc:
                self._raise_if_permanent(exc)
                logger.warning(
                    'checkpoint %s: %s of step %d failed (%r); falling '
                    'back to the next older retained step',
                    self.model_path, what, step, exc)
                failed.append((manager, step, exc))
                continue
            for failed_manager, failed_step, _exc in failed:
                self._quarantine(failed_manager, failed_step)
            return restored
        last_exc = failed[-1][2]
        raise ValueError(
            'No retained checkpoint under `%s` could be restored (all %d '
            'candidate step(s) failed identically-or-worse, so nothing '
            'was quarantined — suspect a config/environment cause); '
            'newest failure: %r' % (self.model_path, len(candidates),
                                    last_exc)) from last_exc

    def _restore_training_at(self, manager, latest: int, abstract_params,
                             abstract_opt_state) -> RestoredTraining:
        """One restore attempt against one (manager, step) artifact."""
        # One metadata read serves both adaptations (it can be disk/network
        # I/O on remote checkpoint stores); the cache keeps
        # _artifact_target_rows' call-on-demand signature.
        _meta_cache = []

        def read_metadata():
            if not _meta_cache:
                _meta_cache.append(manager.item_metadata(latest))
            return _meta_cache[0]

        stored_rows = self._artifact_target_rows(read_metadata)
        # Adapt the restore target to the STORED moment dtypes: the
        # ADAM_MU_DTYPE default flip (fp32 -> bf16, 2026-07-31) — and the
        # ADAM_NU_DTYPE knob gated on the same A/B rule — must not turn a
        # default-config resume of a checkpoint written under the other
        # setting into an opaque dtype-mismatch failure. Restored moments
        # are cast back to the configured dtype below.
        moment_mismatch = {}   # field -> stored dtype
        for field in _MOMENT_FIELDS:
            try:
                stored_dt = _moment_dtype_from_metadata(read_metadata(),
                                                        field)
            except Exception:
                stored_dt = None
            configured_dt = _moment_dtype_of(abstract_opt_state, field)
            if (stored_dt is not None and configured_dt is not None
                    and stored_dt != configured_dt):
                moment_mismatch[field] = stored_dt
        current_params, current_opt = abstract_params, abstract_opt_state
        if stored_rows is not None:
            abstract_params = _with_target_rows(abstract_params, stored_rows)
            abstract_opt_state = _with_target_rows(abstract_opt_state,
                                                   stored_rows)
        for field, stored_dt in moment_mismatch.items():
            logger.warning(
                'checkpoint %s stores Adam %s as %s but the configured '
                'ADAM_%s_DTYPE differs: restoring as stored, then casting '
                '(set --adam-%s-dtype %s to resume bit-exactly)',
                self.model_path, field, stored_dt, field.upper(), field,
                stored_dt.name)
            abstract_opt_state = _with_moment_dtype(abstract_opt_state,
                                                    stored_dt, field)
        target = {'params': abstract_params, 'opt_state': abstract_opt_state,
                  'step': np.asarray(0, np.int32),
                  'epoch': np.asarray(0, np.int32)}
        # failures propagate to restore_training's candidate loop, which
        # distinguishes store-wide config errors (_raise_if_permanent)
        # from per-artifact corruption (quarantine + fall back)
        try:
            restored = manager.restore(
                latest, args=ocp.args.StandardRestore(target))
        except Exception as exc:
            self._raise_if_foreign_optimizer(exc, read_metadata,
                                             abstract_opt_state)
            raise
        params, opt_state = restored['params'], restored['opt_state']
        if stored_rows is not None:
            current_rows = self.metadata.get(_TARGET_ROWS_KEY)
            if current_rows is not None and current_rows != stored_rows:
                params = _resize_target_rows(params, current_params,
                                             current_rows)
                opt_state = _resize_target_rows(opt_state, current_opt,
                                                current_rows)
        for field in moment_mismatch:
            opt_state = _cast_moment(opt_state, current_opt, field)
        return RestoredTraining(
            params=params, opt_state=opt_state,
            step=int(restored['step']), epoch=int(restored['epoch']))

    def _params_adapters(self, abstract_params):
        """(with_rows, adapt) closures of the params-only restore paths:
        target the SAVED target-table row count, then pad/slice back to
        the current allocation (module-level row-adaptation note)."""
        current_params = abstract_params

        def with_rows(stored_rows):
            if stored_rows is not None:
                return _with_target_rows(current_params, stored_rows)
            return current_params

        def adapt(params, stored_rows):
            current_rows = self.metadata.get(_TARGET_ROWS_KEY)
            if (stored_rows is not None and current_rows is not None
                    and current_rows != stored_rows):
                return _resize_target_rows(params, current_params,
                                           current_rows)
            return params

        return with_rows, adapt

    def _check_restore_budget(self, abstract_tree, what: str) -> None:
        """HBM-budget precheck at the restore boundary
        (telemetry/memory.py): params-only restores bring up a NEW set
        next to whatever is already resident (the serving rollover
        candidate above all), so the predicted footprint — known
        exactly from the abstract target — is refused typed BEFORE
        orbax allocates anything.  Training resume is exempt: it
        replaces the state it restores into."""
        from code2vec_tpu.telemetry import memory as memory_lib
        memory_lib.ledger().check_budget(
            memory_lib.tree_nbytes(abstract_tree),
            '%s (`%s`)' % (what, self.model_path))

    def restore_params_step(self, abstract_params, step: int) -> Any:
        """Params-only restore pinned to ONE retained step (canaried
        serving rollover: ``ServingEngine.load_params(step)``). Unlike
        ``restore_params`` there is no older-step fallback — the caller
        asked for this step, so a missing or unrestorable artifact is an
        error, not a silent downgrade."""
        self.verify_metadata()
        self._check_restore_budget(abstract_params,
                                   'params restore at step %d' % step)
        with_rows, adapt = self._params_adapters(abstract_params)
        candidates = [(m, s) for m, s in self._restore_candidates()
                      if s == step]
        if not candidates:
            raise ValueError(
                'No retained checkpoint at step %d under `%s` (retained: '
                '%s)' % (step, self.model_path,
                         sorted({s for _m, s
                                 in self._restore_candidates()})))
        return self._restore_with_fallback(
            candidates,
            lambda manager, s: self._restore_params_at(manager, s,
                                                       with_rows, adapt),
            what='params restore at step %d' % step)

    def restore_params(self, abstract_params) -> Optional[Any]:
        """Restore params only: prefer the released weights-only artifact,
        fall back to the newest full checkpoint (reference load order:
        whatever exists under the load path)."""
        self.verify_metadata()
        self._check_restore_budget(abstract_params, 'params-only restore')
        with_rows, adapt = self._params_adapters(abstract_params)

        if os.path.isdir(self.weights_dir):
            checkpointer = ocp.StandardCheckpointer()

            def read_weights_metadata():
                # newer orbax wraps the tree in .item_metadata; 0.7.x
                # returns the metadata tree directly
                meta = checkpointer.metadata(self.weights_dir)
                return getattr(meta, 'item_metadata', meta)

            stored_rows = self._artifact_target_rows(read_weights_metadata)
            restored = checkpointer.restore(
                self.weights_dir, {'params': with_rows(stored_rows)})
            checkpointer.close()
            return adapt(restored['params'], stored_rows)
        candidates = self._restore_candidates()
        if not candidates:
            return None
        return self._restore_with_fallback(
            candidates,
            lambda manager, step: self._restore_params_at(manager, step,
                                                          with_rows, adapt),
            what='params-only restore')

    def _restore_params_at(self, manager, latest: int, with_rows, adapt):
        """One params-only restore attempt against one (manager, step)."""
        stored_rows = self._artifact_target_rows(
            lambda: manager.item_metadata(latest))
        abstract_params = with_rows(stored_rows)
        # partial restore: pull only the params subtree out of a full
        # training checkpoint (the reference's load-for-eval path similarly
        # ignores optimizer slots)
        item = {'params': abstract_params}
        restore_args = ocp.checkpoint_utils.construct_restore_args(item)
        if _PYTREE_PARTIAL_RESTORE:
            restored = manager.restore(
                latest, args=ocp.args.PyTreeRestore(
                    item=item, restore_args=restore_args,
                    partial_restore=True))
        else:
            # orbax 0.7.x: standalone handler on the step's item dir with
            # the transforms={} partial-restore mechanism (module comment)
            item_dir = os.path.join(str(manager.directory), str(latest),
                                    'default')
            checkpointer = ocp.Checkpointer(ocp.PyTreeCheckpointHandler())
            try:
                restored = checkpointer.restore(
                    item_dir, args=ocp.args.PyTreeRestore(
                        item=item, transforms={},
                        restore_args=restore_args))
            finally:
                checkpointer.close()
        self._check_materialized(restored['params'])
        return adapt(restored['params'], stored_rows)

    def _check_materialized(self, params) -> None:
        """partial_restore=True silently leaves target leaves UNRESTORED
        (as ShapeDtypeStructs) when the stored tree doesn't match — e.g. a
        checkpoint in the pre-canonical backend-native layout. Turn that
        into a clear error instead of a downstream 'not a valid JAX type'."""
        unrestored = [
            jax.tree_util.keystr(path)
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
            if isinstance(leaf, jax.ShapeDtypeStruct)]
        if not unrestored:
            return
        stored = self._stored_metadata()
        # CheckpointLayoutError: layout mismatches are store-wide — the
        # corruption fallback must re-raise them, not quarantine through
        # every retained step
        if stored and stored.get('checkpoint_layout') != self._LAYOUT:
            raise CheckpointLayoutError(
                'Checkpoint at `%s` predates the canonical parameter '
                'layout (no checkpoint_layout marker); it cannot be '
                'restored by this version. Re-save it from the version '
                'that wrote it.' % self.model_path)
        raise CheckpointLayoutError(
            'Checkpoint at `%s` did not contain these parameters: %s — '
            'the stored tree does not match the expected canonical '
            'layout.' % (self.model_path, ', '.join(unrestored)))


def abstract_like(tree, shardings=None):
    """ShapeDtypeStruct pytree matching ``tree`` (optionally with shardings)
    for orbax's StandardRestore target."""
    def make(leaf, sharding=None):
        return jax.ShapeDtypeStruct(np.shape(leaf), leaf.dtype,
                                    sharding=sharding)
    if shardings is None:
        return jax.tree_util.tree_map(make, tree)
    return jax.tree_util.tree_map(make, tree, shardings)
