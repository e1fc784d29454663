"""The readings ``minicpm-sala-9b-l16``'s tolerance is set against, made
anew: the reference with one thing wrong, held to the same comparison as the
reference proper.

    chiprun -- python3 -m chipbench.controls_minicpm_sala --seed <n>

runs the cell ``serve-sala-repo-sessions`` as ``chipbench.run`` does (set-up,
the resident sessions, the window, the checked turns' float32 logits through
resident states, pooled keys and chosen blocks), then computes the reference
proper and each control over the checked session's history and judges the
timed path's logits against each by the configuration's written tolerance.
It exits 0 where the reference proper is held and every control is refused:
the tolerance then still lies between its readings.  ``--rehearse-on-cpu``
is the same at the rehearsal's tiny sizes and its loose tolerance, to prove
the path and not the limits (a state's rounding needs tens of thousands of
positions to show).

The controls (``CONTROLS``), each a fault a program could have:

``float8_weights``    every matrix rounded to three mantissa bits, float8's:
                      the nearest precision below the configuration's
                      bfloat16;
``bf16_state``        the recurrent state rounded to bfloat16 after every
                      position, as a cache that kept it in bfloat16 would;
``no_forced_blocks``  the initial block and the window's blocks left to
                      their scores.

All rounding is by bit arithmetic: a cast down and back up is a pair of
converts the chip's compiler drops (the first reading of such a pair was 0).
The faults are planted here and in no shipped file: the reference and the
runner know nothing of them.  ``tests/test_lm_hybrid.py`` puts the same three
through the judge at a tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import math
import sys
from typing import Callable, Dict

import numpy as np

CONTROLS = ('float8_weights', 'bf16_state', 'no_forced_blocks')


def rounded(x, mantissa_bits: int):
    """float32 ``x`` rounded to ``mantissa_bits`` of mantissa, to nearest
    even, in ``x``'s own dtype."""
    import jax
    import jax.numpy as jnp
    drop = 23 - mantissa_bits
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32((1 << (drop - 1)) - 1) + ((bits >> drop) & 1)) \
        & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(x.dtype)


def recurrence_with_a_bfloat16_state(q, k, v, gamma):
    """The reference's recurrence, its state rounded to bfloat16's seven
    mantissa bits after every position."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision('highest'):
        heads, d = q.shape[1], q.shape[2]

        def one(state, qkv):
            qt, kt, vt = qkv
            state = rounded(gamma[:, None, None] * state
                            + kt[:, :, None] * vt[:, None, :], 7)
            return state, jnp.einsum('hd,hde->he', qt, state) / math.sqrt(d)
        _, out = jax.lax.scan(one, jnp.zeros((heads, d, d), jnp.float32),
                              (q, k, v))
        return out


@contextlib.contextmanager
def swapped(module, name: str, value):
    kept = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, kept)


def references(model_config: dict, params) -> Dict[str, Callable]:
    """{name: f(history, rows) -> float32 logits}: ``'reference'`` (the
    reference proper) and every control."""
    import jax
    from chipbench import reference_minicpm_sala as ref
    from chipbench.runners.serve_lm_sessions import reference_weights

    def forward(config=model_config, each=None, ends=None):
        def run(history, rows):
            weights = reference_weights(params, config, each)
            if ends is not None:
                weights = weights._replace(embed=ends(weights.embed),
                                           head=ends(weights.head))
            return np.asarray(ref.forward(config, weights, history,
                                          logit_positions=rows))
        return run

    float8 = jax.jit(lambda w: rounded(w, 3))

    def low(layer):
        return layer._replace(**{
            field: float8(getattr(layer, field)) for field in layer._fields
            if getattr(getattr(layer, field), 'ndim', 0) == 2})

    def bf16_state(history, rows):
        with swapped(ref, 'recurrence',
                     jax.jit(recurrence_with_a_bfloat16_state)):
            return forward()(history, rows)
    sparse = dict(ref.SPARSE_CONFIG, **model_config.get('sparse_config', {}))
    # no block is the initial one, and the window lies wholly ahead of the
    # query: nothing is forced
    unforced = dict(model_config, sparse_config=dict(
        sparse, init_blocks=0, window_size=-int(sparse['block_size'])))
    return {'reference': forward(),
            'float8_weights': forward(each=low, ends=float8),
            'bf16_state': bf16_state,
            'no_forced_blocks': forward(config=unforced)}


def readings(model_config: dict, params, history, rows, timed, tolerance,
             log=print) -> Dict[str, list]:
    """{name: what of ``tolerance`` the timed path's logits break against
    that reference} (an empty list: held), logging each reading."""
    from chipbench.runners.serve_lm import compare_logits, judge
    out = {}
    for name, compute in references(model_config, params).items():
        error = compare_logits(timed, compute(history, rows))
        out[name] = judge(error, tolerance)
        log('control %s: the timed path against it is off by at most %.4g, '
            'quantiles 5/25/50/75/95 %s, beyond 0.03/0.04/0.05/0.06/0.08 '
            '%s: %s'
            % (name, error.max(),
               np.round(np.percentile(error, [5, 25, 50, 75, 95]), 4),
               [round(float((error > b).mean()), 3)
                for b in (0.03, 0.04, 0.05, 0.06, 0.08)],
               out[name] or 'held'))
    return out


def main(argv=None) -> int:
    from chipbench import manifest, run as run_lib
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', default='serve-sala-repo-sessions')
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, default=20.0)
    parser.add_argument('--rehearse-on-cpu', action='store_true')
    parser.add_argument('--manifest', default=manifest.DEFAULT_MANIFEST)
    args = parser.parse_args(argv)
    started = run_lib.start(args.workload, args.seed, False,
                            args.rehearse_on_cpu, args.manifest)
    if started is None:
        return 3
    ctx, runner = started.ctx, started.runner
    runner.setup()
    try:
        runner.warm()
        runner.measure(args.seconds)
        timed = runner.timed_logits()
        if timed is None:
            ctx.log('the checked turns were not answered: no reading')
            return 1
        history, rows, got = timed
        verdicts = readings(runner.model_config, runner.model.params,
                            history, rows, got,
                            ctx.config['check']['tolerance'], ctx.log)
    finally:
        runner.close()
    held = not verdicts['reference']
    refused = [name for name in CONTROLS if verdicts[name]]
    ctx.log('controls: the reference proper is %s; refused: %s; held though '
            'wrong: %s' % ('held' if held else 'REFUSED', refused,
                           [n for n in CONTROLS if n not in refused]))
    if args.rehearse_on_cpu:    # the path, not the limits
        return 0 if held else 1
    return 0 if held and len(refused) == len(CONTROLS) else 1


if __name__ == '__main__':
    sys.exit(main())
