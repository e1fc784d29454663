"""Device mesh + sharding layout: parallelism as configuration.

The reference is strictly single-device — one ``tf.Session``, no
``tf.distribute``, no collectives anywhere (SURVEY.md §2.3). Here
parallelism is a first-class component, expressed the TPU way: a 2-D
``jax.sharding.Mesh`` with axes

- ``data``  — batch (DP). Gradients are psum-reduced over ICI by XLA because
  params are replicated along this axis.
- ``model`` — parameter sharding (TP). The three embedding tables
  (1.3M/911K/261K rows at full java14m scale, config.py:61-63) are
  row-sharded; the target-embedding sharding also column-shards the final
  softmax logits, so the 261K-way softmax + top-k is computed shard-wise
  with an XLA-inserted collective merge.

Nothing in the model code mentions devices: arrays are *placed* with a
``NamedSharding`` and ``jit`` propagates layouts / inserts collectives
(psum for the DP gradient reduction, all-gather / reduce-scatter around the
sharded gathers and the logits matmul). Multi-host follows the same code
path — ``jax.devices()`` spans hosts and ICI/DCN routing is XLA's job.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from code2vec_tpu.config import Config
from code2vec_tpu.models.functional import Code2VecParams

DATA_AXIS = 'data'
MODEL_AXIS = 'model'


def mesh_platform(mesh: Optional[Mesh] = None) -> str:
    """Platform of the devices a program will run on: the mesh's when one
    is given, the default backend's otherwise. Backend-init errors
    propagate — nothing may pick a code path by catching them."""
    device = (mesh.devices.flat[0] if mesh is not None
              else jax.devices()[0])
    return device.platform.lower()


def create_mesh(config: Optional[Config] = None,
                devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the (data, model) mesh. ``MESH_DATA_AXIS_SIZE == -1`` means
    'all devices not used by the model axis'.

    ``MESH_DEVICE_INDICES`` (comma-separated indices into
    ``jax.devices()``) restricts the mesh to a device SLICE — how a
    placement-pinned serving-mesh worker builds its sub-mesh over the
    chips its slice owns instead of time-sharing the host's full set
    (SERVING.md "Elastic fleet"). An explicit ``devices`` argument wins
    over the config knob."""
    if devices is None and config is not None and \
            getattr(config, 'MESH_DEVICE_INDICES', ''):
        devices = device_slice(config.MESH_DEVICE_INDICES)
    devices = list(devices if devices is not None else jax.devices())
    model_size = config.MESH_MODEL_AXIS_SIZE if config else 1
    data_size = config.MESH_DATA_AXIS_SIZE if config else -1
    if model_size <= 0:
        model_size = 1
    if data_size <= 0:
        data_size = len(devices) // model_size
    if data_size * model_size != len(devices):
        raise ValueError(
            'Mesh {}x{} does not match {} visible devices.'.format(
                data_size, model_size, len(devices)))
    device_grid = np.asarray(devices).reshape(data_size, model_size)
    return Mesh(device_grid, (DATA_AXIS, MODEL_AXIS))


def device_slice(indices: str) -> list:
    """Resolve a comma-separated index spec ('0,1,2') against
    ``jax.devices()``; raises on malformed, duplicate, or out-of-range
    indices so a misplaced worker fails its handshake typed instead of
    silently building a mesh over the wrong chips."""
    try:
        idx = [int(tok) for tok in indices.split(',') if tok.strip()]
    except ValueError:
        raise ValueError(
            'MESH_DEVICE_INDICES must be comma-separated integers, got '
            '{!r}.'.format(indices))
    if not idx:
        raise ValueError('MESH_DEVICE_INDICES resolved to an empty '
                         'device slice: {!r}.'.format(indices))
    if len(set(idx)) != len(idx):
        raise ValueError('MESH_DEVICE_INDICES has duplicate indices: '
                         '{!r}.'.format(indices))
    all_devices = jax.devices()
    bad = [i for i in idx if i < 0 or i >= len(all_devices)]
    if bad:
        raise ValueError(
            'MESH_DEVICE_INDICES {!r} out of range for {} visible '
            'devices.'.format(bad, len(all_devices)))
    return [all_devices[i] for i in idx]


def partition_device_indices(n_slices: int, per_slice: int) -> list:
    """Partition ``jax.devices()`` index space into ``n_slices``
    DISJOINT contiguous slices of ``per_slice`` devices each — the
    serving mesh's placement table (one slice per replica). Raises when
    the host doesn't have enough devices; contiguity keeps a slice's
    chips ICI-adjacent under the usual host enumeration order."""
    total = len(jax.devices())
    if n_slices * per_slice > total:
        raise ValueError(
            'Placement wants {} slices x {} devices but only {} are '
            'visible (MESH_DEVICES_PER_REPLICA too big for the '
            'replica count).'.format(n_slices, per_slice, total))
    return [list(range(s * per_slice, (s + 1) * per_slice))
            for s in range(n_slices)]


def param_specs() -> Code2VecParams:
    """PartitionSpecs for the five parameter arrays: embedding tables
    row-sharded over ``model``; the small dense/attention params replicated
    (SURVEY.md §2.3 'TPU-native equivalent to build')."""
    return Code2VecParams(
        token_embedding=P(MODEL_AXIS, None),
        path_embedding=P(MODEL_AXIS, None),
        target_embedding=P(MODEL_AXIS, None),
        transform=P(None, None),
        attention=P(None, None),
    )


def batch_spec(ndim: int = 1, shard_contexts: bool = False) -> P:
    """Per-example arrays shard over the batch (data) axis; with
    ``shard_contexts``, 2-D (batch, contexts) arrays additionally shard the
    contexts axis over the model axis — order-free sequence parallelism for
    large bags (the attention reductions compile to XLA collectives).

    Exactly 2-D: the 3-D packed ctx buffer (data/packed.py) is per-shard
    data whose capacity dim must NOT split over the model axis — each
    device holds its own shard's full context stream."""
    if ndim == 2 and shard_contexts:
        return P(DATA_AXIS, MODEL_AXIS)
    return P(DATA_AXIS)


def param_sharding(mesh: Mesh) -> Code2VecParams:
    specs = param_specs()
    return Code2VecParams(*[NamedSharding(mesh, spec) for spec in specs])


def shard_params(params, mesh: Mesh):
    """Place a (possibly host-local) parameter pytree onto the mesh.

    Works for both backends: leaves are matched to their PartitionSpec by
    *name* (the last path component), so the flax ``{'params': {...}}`` dict
    and the raw ``Code2VecParams`` NamedTuple both work regardless of
    flatten order."""
    shardings_by_name = param_sharding(mesh)._asdict()
    path_leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    placed = []
    for path, leaf in path_leaves:
        name = _leaf_name(path)
        if name not in shardings_by_name:
            raise ValueError('Unknown parameter leaf {!r}; expected one of '
                             '{}'.format(name, sorted(shardings_by_name)))
        placed.append(jax.device_put(leaf, shardings_by_name[name]))
    return jax.tree_util.tree_unflatten(treedef, placed)


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, 'key', None) or getattr(last, 'name', str(last))


def sharding_for_tree(tree, mesh: Mesh, zero_partition: bool = False):
    """Shardings for an arbitrary pytree whose leaves either *are* model
    parameters (matched by leaf name, wherever they sit — e.g. inside Adam's
    ``mu``/``nu`` moment trees) or are small scalars/state (replicated).

    This is how optimizer state inherits the parameter layout without any
    per-optimizer code.

    ``zero_partition`` (ZeRO-1-style, ``Config.OPTIMIZER_STATE_SHARDING=
    'zero'``): leaves that would be row-sharded over ``model`` only are
    instead row-sharded over the WHOLE mesh ``(data, model)`` — per-device
    bytes drop by the data-axis size, and XLA turns the consuming update
    into the reduce-scatter/all-gather pair it places itself. Only
    meaningful for the moment trees (params must keep their own layout,
    so never pass it for a parameter pytree)."""
    shardings_by_name = param_sharding(mesh)._asdict()
    if zero_partition:
        zero = NamedSharding(mesh, P((DATA_AXIS, MODEL_AXIS), None))
        shardings_by_name = {
            name: zero if s.spec == P(MODEL_AXIS, None) else s
            for name, s in shardings_by_name.items()}
    replicated = NamedSharding(mesh, P())
    path_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = [shardings_by_name.get(_leaf_name(path), replicated)
           for path, _leaf in path_leaves]
    return jax.tree_util.tree_unflatten(treedef, out)


def attach_shardings(abstract_tree, mesh: Mesh, zero_partition: bool = False):
    """ShapeDtypeStruct pytree → same pytree with mesh shardings attached
    (the restore target orbax needs to re-shard onto the *current* mesh)."""
    shardings = sharding_for_tree(abstract_tree, mesh, zero_partition)
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                             sharding=s),
        abstract_tree, shardings)


def local_rows(array: jax.Array) -> np.ndarray:
    """Host numpy view of THIS process's rows of a batch-sharded output.

    Multi-host eval pairs device outputs (top-k indices) with host-side
    strings (labels) that only the producing process holds, so each process
    must read back exactly the rows it fed in via
    ``make_array_from_process_local_data``.  Addressable shards are
    deduplicated (model-axis replicas carry identical rows) and stitched in
    ascending global-row order — the order the local batch was provided in.
    """
    if array.is_fully_addressable:
        return np.asarray(array)
    blocks: dict = {}
    for shard in array.addressable_shards:
        index = shard.index
        row0 = (index[0].start or 0) if index else 0
        col0 = (index[1].start or 0) if len(index) > 1 else 0
        cols = blocks.setdefault(row0, {})
        if col0 not in cols:  # skip D2H copies of model-axis replicas
            cols[col0] = np.asarray(shard.data)
    row_blocks = []
    for row0 in sorted(blocks):
        cols = blocks[row0]
        row_blocks.append(
            np.concatenate([cols[c] for c in sorted(cols)], axis=1)
            if len(cols) > 1 else next(iter(cols.values())))
    return np.concatenate(row_blocks, axis=0)


def shard_batch(arrays, mesh: Mesh, shard_contexts: bool = False,
                direct: bool = False):
    """Place a tuple of per-example numpy arrays onto the mesh: batch over
    ``data``; optionally contexts over ``model`` for 2-D arrays.

    ``direct=True`` (the trainer's staging ring) slices each array into
    its per-device shards on the host and issues one batched
    ``device_put`` of the slices straight to their devices, then stitches
    the global array with ``make_array_from_single_device_arrays`` — each
    data-parallel shard crosses the wire exactly once, to its own device,
    instead of relying on the runtime's whole-array placement (which may
    replicate-then-slice through a transfer-bound link). Equal values
    and shardings either way (tests/test_packed.py).

    Multi-host: each process holds its LOCAL 1/process_count share of the
    global batch (the reader strides the data file per process);
    ``make_array_from_process_local_data`` assembles the global sharded
    array without any cross-host copy."""
    if jax.process_count() > 1:
        out = []
        for a in arrays:
            sharding = NamedSharding(mesh,
                                     batch_spec(np.ndim(a), shard_contexts))
            global_shape = ((a.shape[0] * jax.process_count(),)
                            + tuple(a.shape[1:]))
            out.append(jax.make_array_from_process_local_data(
                sharding, np.asarray(a), global_shape))
        return tuple(out)
    if direct and mesh.size > 1:
        from code2vec_tpu.telemetry import core as tele_core
        out = []
        for a in arrays:
            a = np.asarray(a)
            sharding = NamedSharding(mesh,
                                     batch_spec(a.ndim, shard_contexts))
            index_map = sharding.addressable_devices_indices_map(a.shape)
            devices = list(index_map)
            if tele_core.enabled():
                # named scope so per-shard placement slicing shows up
                # against the device lanes in a profiler capture
                with jax.profiler.TraceAnnotation('host/shard_slice'):
                    slices = [np.ascontiguousarray(a[index_map[d]])
                              for d in devices]
            else:
                slices = [np.ascontiguousarray(a[index_map[d]])
                          for d in devices]
            pieces = jax.device_put(slices, devices)
            out.append(jax.make_array_from_single_device_arrays(
                a.shape, sharding, pieces))
        return tuple(out)
    return tuple(
        jax.device_put(a, NamedSharding(
            mesh, batch_spec(np.ndim(a), shard_contexts)))
        for a in arrays)
