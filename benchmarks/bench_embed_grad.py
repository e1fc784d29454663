"""On-chip A/B: embedding-table gradient strategies (ops/embed_grad.py).

Measures the full java14m train step under EMBED_GRAD_IMPL in {'dense',
'sorted', 'dedup'} over two index distributions:

- uniform — benchlib.random_batches, the headline bench's synthetic data
  (~93% of gathered token rows unique: dedup has little to combine);
- zipf    — Zipf(1.3)-distributed indices, matching how real corpora hit
  the frequency-ordered vocab (code2vec vocabs are built most-frequent-
  first, so hot rows cluster at low indices); most draws repeat, which is
  the case 'dedup' exists for.

Same chained devargs/sync-at-end methodology as the other harnesses
(PERF.md); prints one JSON line per measurement.
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from code2vec_tpu import benchlib  # noqa: E402

SMOKE = benchlib.smoke_requested()
SHAPES = benchlib.SMOKE_SHAPES if SMOKE else benchlib.JAVA14M
WARMUP, STEPS = benchlib.bench_steps(SMOKE)


def zipf_batches(shapes, n: int, seed: int = 0, a: float = 1.3):
    """Synthetic batches whose indices follow a Zipf law over the vocab,
    approximating real frequency-ordered corpus hits."""
    from code2vec_tpu.data.reader import Batch
    rng = np.random.default_rng(seed)

    def draw(vocab, size):
        raw = rng.zipf(a, size=size).astype(np.int64)
        return (1 + (raw - 1) % (vocab - 1)).astype(np.int32)

    batch, contexts = shapes.batch_size, shapes.max_contexts
    return [Batch(
        source=draw(shapes.token_vocab, (batch, contexts)),
        path=draw(shapes.path_vocab, (batch, contexts)),
        target=draw(shapes.token_vocab, (batch, contexts)),
        mask=np.ones((batch, contexts), np.float32),
        label=draw(shapes.target_vocab, (batch,)),
        weight=np.ones((batch,), np.float32)) for _ in range(n)]


def measure(label: str, host_batches, **overrides) -> None:
    config = benchlib.headline_config(SHAPES, **overrides)
    trainer, state = benchlib.build_trainer(config, SHAPES)
    feeds = benchlib.staged(trainer, host_batches)
    for i in range(WARMUP):
        state, loss = trainer.train_step_placed(state, feeds[i % len(feeds)])
        float(loss)
    t0 = time.perf_counter()
    last = None
    for i in range(STEPS):
        state, last = trainer.train_step_placed(state, feeds[i % len(feeds)])
    float(last)
    dt = (time.perf_counter() - t0) / STEPS
    if SMOKE:
        label += '_SMOKE_ONLY'
    print(json.dumps({'measure': label, 'value': round(dt * 1e3, 2),
                      'examples_per_sec': round(SHAPES.batch_size / dt, 1)}),
          flush=True)


def main() -> None:
    import jax

    print(json.dumps({'platform': jax.devices()[0].platform.lower()}),
          flush=True)
    uniform = benchlib.random_batches(SHAPES, 4)
    zipf = zipf_batches(SHAPES, 4)
    # duplicate-rate context so the verdict is interpretable
    for name, batches in (('uniform', uniform), ('zipf', zipf)):
        tok = np.concatenate([np.asarray(b.source).ravel() for b in batches[:1]]
                             + [np.asarray(b.target).ravel()
                                for b in batches[:1]])
        print(json.dumps({'measure': f'unique_token_rows_frac_{name}',
                          'value': round(len(np.unique(tok)) / tok.size, 4)}),
              flush=True)
    # Arms pin the threefry + fp32-mu baseline knobs: the config DEFAULTS
    # flipped to rbg + bf16 mu on the 2026-07-31 capture, and a re-run
    # must stay comparable with the recorded 2026-07-31 series the
    # EMBED_GRAD_IMPL='dense' verdict cites (PERF.md).
    pins = dict(DROPOUT_PRNG_IMPL='threefry2x32', ADAM_MU_DTYPE='float32',
                ADAM_NU_DTYPE='float32', GRADS_DTYPE='float32')
    for impl in ('dense', 'sorted', 'dedup'):
        measure(f'step_ms_embed_grad_{impl}_uniform', uniform,
                EMBED_GRAD_IMPL=impl, **pins)
    for impl in ('dense', 'sorted', 'dedup'):
        measure(f'step_ms_embed_grad_{impl}_zipf', zipf,
                EMBED_GRAD_IMPL=impl, **pins)


if __name__ == '__main__':
    main()
