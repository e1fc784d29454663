"""Diagnose where the on-chip train-step time goes (host link vs compute).

Round-2 context: the first driver-captured bench number was 2,420
examples/sec/chip (0.52x V100) at ~423 ms/step, far above the ~25 ms/step
roofline estimate (0.9 TFLOP matmul work + ~11 GB HBM traffic for the dense
Adam update over 384M params).  This script separates:

  rtt            host->device->host round-trip latency of a trivial op
  h2d            per-step batch upload cost (numpy args vs device-resident)
  sync-per-step  the round-1 bench's per-step float(loss) sync
  sync-at-end    enqueue N steps, block once on the final loss
  staged         end-to-end host batches through Trainer.stage_batches

Prints one JSON line per measurement.  Run on the real chip; measured
results are recorded in PERF.md.
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from code2vec_tpu import benchlib  # noqa: E402

# BENCH_SMOKE=1: tiny shapes so the ladder itself can be validated on
# CPU (same convention as bench.py); real captures use java14m shapes.
SMOKE = benchlib.smoke_requested()
SHAPES = benchlib.SMOKE_SHAPES if SMOKE else benchlib.JAVA14M
WARMUP = 1 if SMOKE else 5
STEPS = 4 if SMOKE else 20


def main() -> None:
    import numpy as np

    import jax

    print(json.dumps({'platform': jax.devices()[0].platform.lower()}),
          flush=True)

    # --- host<->device round-trip latency on a trivial op
    tiny = jax.jit(lambda x: x + 1)
    v = tiny(jax.numpy.zeros(()))
    float(v)
    sw = benchlib.bench_timer('rtt')
    with sw.time():
        for _ in range(20):
            float(tiny(v))
    rtt = sw.last / 20
    print(json.dumps({'measure': 'rtt_trivial_op_ms',
                      'value': round(rtt * 1e3, 2)}), flush=True)

    # The diag ladder's baseline is pinned to threefry dropout + fp32 mu:
    # the config DEFAULTS flipped to 'rbg' + bf16 mu on this ladder's own
    # 2026-07-31 capture, and every variant delta below (no_dropout's
    # ~4.8 ms threefry cost, the rbg_dropout and bf16_mu arms themselves)
    # is defined relative to the threefry/fp32-mu-era baseline the PERF.md
    # tables record. Without the pins a variant equal to the new defaults
    # would measure default-vs-default (~0 delta) and new captures would
    # be incomparable with the 2026-07-29 series.
    BASELINE_PINS = dict(DROPOUT_PRNG_IMPL='threefry2x32',
                         ADAM_MU_DTYPE='float32',
                         ADAM_NU_DTYPE='float32', GRADS_DTYPE='float32')
    config = benchlib.headline_config(SHAPES, **BASELINE_PINS)
    trainer, state = benchlib.build_trainer(config, SHAPES)
    host_batches = benchlib.random_batches(SHAPES, 4)

    # --- upload cost for one batch
    sw = benchlib.bench_timer('h2d')
    with sw.time():
        dev_batches = [jax.block_until_ready(arrays) for arrays, _ in
                       trainer.stage_batches(iter(host_batches))]
    h2d = sw.last / len(host_batches)
    print(json.dumps({'measure': 'h2d_one_batch_ms',
                      'value': round(h2d * 1e3, 2)}), flush=True)

    # --- wire format: bytes/batch + upload cost, planes vs packed, at
    # the REALISTIC java14m fill (full-fill batches would hide the win —
    # the packed size tracks the corpus fill rate; the compute numbers
    # above keep full batches for comparability with prior captures)
    filled = benchlib.random_batches(SHAPES, 4, seed=2,
                                     fill=benchlib.JAVA14M_FILL)
    for wire_label, wire_batches in (
            ('planes', filled),
            ('packed', benchlib.pack_batches(filled, trainer))):
        print(json.dumps({'measure': 'wire_bytes_per_batch',
                          'format': wire_label,
                          'value': benchlib.wire_bytes(wire_batches[0])}),
              flush=True)
        with sw.time():
            for arrays, _b in trainer.stage_batches(iter(wire_batches)):
                jax.block_until_ready(arrays)
        dt = sw.last / len(wire_batches)
        print(json.dumps({'measure': 'h2d_one_batch_%s_ms' % wire_label,
                          'value': round(dt * 1e3, 2)}), flush=True)

    # --- per-shard h2d: each data shard's slice of the packed ctx buffer
    # timed onto its own device (the direct placement stage_batches uses)
    from jax.sharding import NamedSharding

    from code2vec_tpu.parallel import mesh as mesh_lib
    ctx = benchlib.pack_batches(filled[:1], trainer)[0].ctx
    sharding = NamedSharding(trainer.mesh, mesh_lib.batch_spec(ctx.ndim))
    per_shard = []
    for device, index in sharding.addressable_devices_indices_map(
            ctx.shape).items():
        piece = np.ascontiguousarray(ctx[index])
        with sw.time():
            jax.block_until_ready(jax.device_put(piece, device))
        per_shard.append(round(sw.last * 1e3, 2))
    print(json.dumps({'measure': 'h2d_per_shard_ms', 'format': 'packed',
                      'n_shards': len(per_shard), 'values': per_shard}),
          flush=True)

    def timed(label, step_fn, init_state, feeds, sync_each):
        """Warmup + measure one step function; returns the final state so
        variants can keep training off their own state.  sync_each times
        every step individually (per-step stats via the shared Timer);
        sync_end times the whole enqueued window and amortizes the one
        blocking sync."""
        st = init_state
        for i in range(WARMUP):
            st, loss = step_fn(st, feeds[i % len(feeds)])
            float(loss)
        timer = benchlib.bench_timer(label)
        last = None
        if sync_each:
            for i in range(STEPS):
                with timer.time():
                    st, last = step_fn(st, feeds[i % len(feeds)])
                    float(last)
            dt = timer.total / STEPS
        else:
            with timer.time():
                for i in range(STEPS):
                    st, last = step_fn(st, feeds[i % len(feeds)])
                float(last)
            dt = timer.last / STEPS
        print(json.dumps(
            {'measure': label, 'value': round(dt * 1e3, 2),
             'examples_per_sec': round(SHAPES.batch_size / dt, 1)}),
            flush=True)
        return st

    state = timed('step_ms_hostargs_sync_each', trainer.train_step, state,
                  host_batches, True)
    state = timed('step_ms_devargs_sync_each', trainer.train_step_placed,
                  state, dev_batches, True)
    state = timed('step_ms_devargs_sync_end', trainer.train_step_placed,
                  state, dev_batches, False)
    state = timed('step_ms_hostargs_sync_end', trainer.train_step, state,
                  host_batches, False)

    # --- is the per-batch upload bandwidth- or latency-bound?  One
    # contiguous array of the same total byte size:
    total_bytes = sum(np.asarray(a).nbytes for a in host_batches[0])
    flat = np.zeros(total_bytes // 4, np.int32)
    jax.block_until_ready(jax.device_put(flat))
    with sw.time():
        for _ in range(5):
            jax.block_until_ready(jax.device_put(flat))
    print(json.dumps({'measure': 'h2d_packed_same_bytes_ms',
                      'value': round(sw.last / 5 * 1e3, 2)}), flush=True)

    # --- does stage_batches overlap uploads behind compute end-to-end?
    fresh = benchlib.random_batches(SHAPES, STEPS, seed=1)
    last = None
    with sw.time():
        for arrays, _b in trainer.stage_batches(iter(fresh)):
            state, last = trainer.train_step_placed(state, arrays)
        float(last)
    dt = sw.last / STEPS
    print(json.dumps(
        {'measure': 'step_ms_staged_hostargs_end_to_end',
         'value': round(dt * 1e3, 2),
         'examples_per_sec': round(SHAPES.batch_size / dt, 1)}), flush=True)

    # --- the same end-to-end staging at REALISTIC fill, both wire
    # formats: (filled - packed) is the transfer time the packed wire
    # buys per step in the transfer-bound regime. The packed arm warms
    # its program (the jitted unpack+step twin) outside the timed window.
    filled_feed = benchlib.random_batches(SHAPES, STEPS, seed=3,
                                          fill=benchlib.JAVA14M_FILL)
    packed_feed = benchlib.pack_batches(filled_feed, trainer)
    # warm with a batch from the SAME feed: pack_batches pins one shared
    # capacity, so this is the exact program the timed loop runs
    for arrays, _b in trainer.stage_batches(iter(packed_feed[:1])):
        state, last = trainer.train_step_placed(state, arrays)
    float(last)
    for wire_label, feed in (('filled', filled_feed),
                             ('packed', packed_feed)):
        last = None
        with sw.time():
            for arrays, _b in trainer.stage_batches(iter(feed)):
                state, last = trainer.train_step_placed(state, arrays)
            float(last)
        dt = sw.last / STEPS
        print(json.dumps(
            {'measure': 'step_ms_staged_hostargs_%s' % wire_label,
             'value': round(dt * 1e3, 2),
             'examples_per_sec': round(SHAPES.batch_size / dt, 1)}),
            flush=True)

    # --- config-variant A/Bs, one fresh trainer each. The previous
    # variant's 4.6 GB state is freed before the next is built; memory
    # stays within one trainer + one variant at a time.
    state = dev_batches = fresh = trainer = None  # noqa: F841
    # Each variant = BASELINE_PINS with exactly one knob changed, so every
    # delta is attributable to its label even as config defaults move.
    variants = [
        # how much of the step is the dropout mask's threefry RNG?
        # (B=1024, C=200, 3d=640 -> 131M bernoulli draws per step)
        ('step_ms_devargs_sync_end_no_dropout',
         dict(DROPOUT_KEEP_RATE=1.0)),
        # lazy (sparse-row) Adam for the token/path tables: does cutting
        # the optimizer's O(vocab) HBM walk to O(touched rows) pay?
        # (measured 2026-07-29: 90.85 ms vs dense 49.25 — it does not)
        ('step_ms_devargs_sync_end_lazy_adam',
         dict(LAZY_EMBEDDING_ADAM=True)),
        # hardware RngBitGenerator for the dropout mask vs the ~4.8 ms of
        # threefry the no-dropout variant exposed
        ('step_ms_devargs_sync_end_rbg_dropout',
         dict(DROPOUT_PRNG_IMPL='rbg')),
        # bf16 first moment: ~1.5 GB/step less HBM traffic in the dense
        # Adam update
        ('step_ms_devargs_sync_end_bf16_mu',
         dict(ADAM_MU_DTYPE='bfloat16')),
    ]
    for label, overrides in variants:
        variant_config = benchlib.headline_config(
            SHAPES, **{**BASELINE_PINS, **overrides})
        variant_trainer, variant_state = benchlib.build_trainer(
            variant_config, SHAPES)
        feeds = benchlib.staged(variant_trainer, host_batches)
        timed(label, variant_trainer.train_step_placed, variant_state,
              feeds, False)
        variant_trainer = variant_state = feeds = None  # noqa: F841

    # --- DIAGNOSTIC (not a product knob): how much of the step is the
    # embedding backward (gather-grad -> scatter-adds into the 1.3M/911K
    # tables)? stop_gradient on the tables removes exactly that from the
    # backward while the forward AND the dense Adam walk over the full
    # tables stay; baseline minus this = the scatter/gather-backward cost
    # the cost-analysis roofline can't itemize.
    import optax

    frozen_config = benchlib.headline_config(SHAPES, **BASELINE_PINS)
    frozen_trainer, frozen_state = benchlib.build_trainer(
        frozen_config, SHAPES)
    feeds = benchlib.staged(frozen_trainer, host_batches)
    backend = frozen_trainer.backend
    frozen_opt = optax.adam(frozen_config.LEARNING_RATE)

    def frozen_tables_step(state, arrays):
        def loss_fn(params):
            stopped = params._replace(
                token_embedding=jax.lax.stop_gradient(params.token_embedding),
                path_embedding=jax.lax.stop_gradient(params.path_embedding))
            loss, _aux = backend.loss_fn(stopped, arrays, jax.random.fold_in(
                state.rng, state.step))
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        updates, new_opt = frozen_opt.update(grads, state.opt_state,
                                             state.params)
        new_params = optax.apply_updates(state.params, updates)
        return state._replace(params=new_params, opt_state=new_opt,
                              step=state.step + 1), loss

    frozen_jit = jax.jit(frozen_tables_step, donate_argnums=(0,))
    timed('step_ms_devargs_sync_end_frozen_tables', frozen_jit,
          frozen_state, feeds, False)
    frozen_trainer = frozen_state = feeds = None  # noqa: F841

    # --- top-k micro A/B: monolithic lax.top_k vs the exact grouped
    # two-stage merge over java14m-shaped logits. Chained by feeding each
    # round's max value back into the input (async dispatch makes
    # unchained timings meaningless).
    import jax.numpy as jnp

    from code2vec_tpu.ops.topk import grouped_top_k

    logits = jax.device_put(np.random.default_rng(0).normal(
        size=(SHAPES.batch_size, 261248)).astype(np.float32))
    jax.block_until_ready(logits)

    def bench_topk(label, fn):
        stepped = jax.jit(lambda x, t: fn(x + t * 0.0, 10))
        token = jnp.zeros((), jnp.float32)
        for _ in range(3):
            values, _ = stepped(logits, token)
            token = values[0, 0]
        float(token)
        topk_sw = benchlib.bench_timer(label)
        with topk_sw.time():
            token = jnp.zeros((), jnp.float32)
            for _ in range(10):
                values, _ = stepped(logits, token)
                token = values[0, 0]
            float(token)
        dt = topk_sw.last / 10
        print(json.dumps({'measure': label, 'value': round(dt * 1e3, 2)}),
              flush=True)

    bench_topk('topk_ms_lax_b1024_v261k', jax.lax.top_k)
    bench_topk('topk_ms_grouped_b1024_v261k', grouped_top_k)


if __name__ == '__main__':
    main()
