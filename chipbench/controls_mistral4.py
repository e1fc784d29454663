"""The readings ``mistral-small-4-119b-ep4-l6``'s tolerance is set against,
made anew: the reference with one thing wrong, held to the same comparison
as the reference proper.

    python3 -m chipbench.controls_mistral4 --seed <n>      (on the chip)

runs the cell ``serve-mistral4-docqa`` as ``chipbench.run`` does (set-up,
the resident sessions, the window, the checked turns' float32 logits
through the expanded chunk path and the absorbed decode kernel), then
computes the reference proper and each control over the checked session's
history and judges the timed path's logits against each by the
configuration's written tolerance.  It exits 0 where the reference proper
is held and every control is refused: the tolerance then still lies
between its readings.  ``--rehearse-on-cpu`` is the same at the
rehearsal's tiny sizes and its loose tolerance, to prove the path and not
the limits.

The controls (``CONTROLS``), each a fault a program could have:

``float8_weights``      every matrix rounded to three mantissa bits,
                        float8's: the nearest precision below the
                        configuration's bfloat16;
``float8_latents``      the latent cache (``[cbar | kr]`` of every position)
                        rounded to float8's mantissa, as a cache kept in
                        float8 would hand it on;
``no_query_scale``      the llama-4 query scale left out
                        (``llama_4_scaling_beta`` 0);
``rotate_half``         rotate-half pairs ``(i, i + 32)`` in place of the
                        interleaved ``(2i, 2i + 1)``.

All rounding is by bit arithmetic (``controls_minicpm_sala.rounded``): a
cast down and back up is a pair of converts the chip's compiler drops.  The
faults are planted here and in no shipped file: the reference and the
runner know nothing of them.  ``tests/test_lm_latent.py`` puts the same
four through the judge at a tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import sys
from typing import Callable, Dict

import numpy as np

from chipbench.controls_minicpm_sala import rounded, swapped

CONTROLS = ('float8_weights', 'float8_latents', 'no_query_scale',
            'rotate_half')


def references(model_config: dict, params) -> Dict[str, Callable]:
    """{name: f(history, rows) -> float32 logits}: ``'reference'`` (the
    reference proper) and every control."""
    import jax
    from chipbench import reference_mistral4 as ref
    from chipbench.runners.serve_lm_latent import reference_weights

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
            if getattr(getattr(layer, field), 'ndim', 0) >= 2})

    plain_latents = ref.latents

    def latents_in_float8(*args, **kwargs):
        return tuple(float8(part) for part in plain_latents(*args, **kwargs))

    def float8_latents(history, rows):
        with swapped(ref, 'latents', latents_in_float8):
            return forward()(history, rows)
    rope = dict(model_config['rope_parameters'])
    return {'reference': forward(),
            'float8_weights': forward(each=low, ends=float8),
            'float8_latents': float8_latents,
            'no_query_scale': forward(config=dict(
                model_config, rope_parameters=dict(
                    rope, llama_4_scaling_beta=0.0))),
            'rotate_half': forward(config=dict(model_config,
                                               rope_interleave=False))}


def readings(model_config: dict, params, history, rows, timed, tolerance,
             log=print) -> Dict[str, list]:
    """{name: what of ``tolerance`` the timed path's logits break against
    that reference} (an empty list: held), logging each reading."""
    from chipbench.runners.serve_lm import compare_logits
    from chipbench.runners.serve_lm_latent import judge
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
    parser.add_argument('--workload', default='serve-mistral4-docqa')
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
