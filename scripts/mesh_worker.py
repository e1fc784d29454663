"""Standalone mesh worker (SERVING.md "Elastic fleet").

The entry point an EXTERNAL orchestrator — a static host list, k8s, a
drill — execs to add capacity to a running socket-mode mesh without
the mesh spawning anything: the worker builds its model (its own
sub-mesh when ``--device-indices`` places it), warms its ladder, dials
the mesh listener at ``--address``, and serves the framed dispatch
wire exactly like a mesh-spawned worker (scripts/../serving/mesh.py
``_replica_worker_main`` IS the serve loop — this script only
assembles its config; the serve loop places the persistent compile cache,
``code2vec_tpu/compile_cache.py``, before the worker's first compile).

Because the rid is one the mesh never registered, the dial-in lands on
``SocketListener``'s unclaimed path and the mesh ADOPTS it: validates
wire proto / batch wire format / warm tiers, re-adopts it onto the
fleet's current params step, and gives it a puller.  Restart
supervision stays HERE (the orchestrator's job): if this process dies
the mesh retires its slot without charging the local restart budget,
and re-execing this script is the restart.

The worker dials FIRST, then cold-starts (model build + warmup), then
sends its ready frame — same order as a mesh-spawned worker — so the
mesh's adoption wait (``ServingMesh.adopt_ready_timeout_s``) covers
the cold start; a worker that wedges before ready is dropped typed
when that wait expires (the ``adopt_stall`` drill's shape).

Usage:
  python scripts/mesh_worker.py --address HOST:PORT --load PATH \\
      [--rid RID] [--device-indices 4,5,6,7] [--tiers topk,vectors] \\
      [--heartbeat-secs S] [--config-json FILE]

``--config-json`` ships a full config-overrides dict (what the mesh
would have shipped at spawn) for orchestrators that template worker
configs; the flags below override it.  config-knob-docs lint note:
these are argparse flags of a script, not package knobs — the knobs
they set (``MESH_DEVICE_INDICES`` et al) are documented in README.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def parse_address(text: str):
    host, _, port = text.rpartition(':')
    if not host or not port.isdigit():
        raise argparse.ArgumentTypeError(
            'expected HOST:PORT, got %r' % text)
    return host, int(port)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description='externally-orchestrated mesh worker: dials a '
                    'socket-mode ServingMesh listener and serves the '
                    'dispatch wire until closed or killed')
    parser.add_argument('--address', required=True, type=parse_address,
                        help='the mesh listener (MESH_SOCKET_HOST:port '
                             'as logged by the mesh at build)')
    parser.add_argument('--rid', default=None,
                        help='replica id to introduce as (default '
                             'ext-<pid>); must be unique in the fleet')
    parser.add_argument('--load', default=None,
                        help='checkpointed model path (at least one '
                             'retained step); required unless '
                             '--config-json carries MODEL_LOAD_PATH')
    parser.add_argument('--config-json', default=None,
                        help='JSON file of Config field overrides (the '
                             'shape the mesh ships at spawn); flags '
                             'here override its entries')
    parser.add_argument('--device-indices', default=None,
                        help='comma-separated indices into '
                             'jax.devices() — this worker\'s placement '
                             'slice (sets MESH_DEVICE_INDICES)')
    parser.add_argument('--tiers', default=None,
                        help='warm-tier ladder (SERVING_WARM_TIERS); '
                             'must cover the mesh\'s tiers or adoption '
                             'is rejected typed')
    parser.add_argument('--heartbeat-secs', type=float, default=None,
                        help='liveness beat period (MESH_HEARTBEAT_'
                             'SECS); match the mesh\'s or its monitor '
                             'mis-reads the beat cadence')
    args = parser.parse_args(argv)

    overrides = {}
    if args.config_json:
        with open(args.config_json) as handle:
            overrides = dict(json.load(handle))
    if args.load:
        overrides['MODEL_LOAD_PATH'] = args.load
    if args.device_indices:
        overrides['MESH_DEVICE_INDICES'] = args.device_indices
    if args.tiers:
        overrides['SERVING_WARM_TIERS'] = args.tiers
    if args.heartbeat_secs is not None:
        overrides['MESH_HEARTBEAT_SECS'] = args.heartbeat_secs
    if not overrides.get('MODEL_LOAD_PATH'):
        parser.error('a worker restores params from a checkpoint '
                     'store: pass --load PATH (or MODEL_LOAD_PATH in '
                     '--config-json)')
    # the worker serves; it must never save, train, or self-roll —
    # rollover arrives over the wire from the mesh's coordinated canary
    overrides['MODEL_SAVE_PATH'] = ''
    overrides['TRAIN_DATA_PATH_PREFIX'] = ''
    overrides['SERVE_FOLLOW_CHECKPOINTS_SECS'] = 0.0
    rid = args.rid if args.rid else 'ext-%d' % os.getpid()

    from code2vec_tpu.serving import mesh as mesh_lib
    # the serve loop is the ONE worker implementation: same handshake,
    # same wire, same fault sites as a mesh-spawned replica
    mesh_lib._replica_worker_main(rid, overrides, None, args.address)
    return 0


if __name__ == '__main__':
    sys.exit(main())
