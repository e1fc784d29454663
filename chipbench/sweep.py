"""Finds the knee of a serving cell, once, when the cell is defined.

    python3 -m chipbench.sweep --workload serve-open --seed 1 \\
        --rates 100,200,300,400 --seconds 8

One process, one engine: the cell's own runner is set up once, then offers
its mix at each rate in turn for ``--seconds`` and prints one JSON line per
rate. A rate is sustained when nothing was shed or failed, the backlog did
not grow (the queue is short at the end, and the second half's median
latency is not well above the first half's) and the generator's median
lateness stayed within the mix's stated share of the mean gap. The
knee is the highest sustained rate; the cell's mix then holds about four
fifths of it as a number (README.md). Never part of a check: the benchmark
offers a fixed rate and searches for none.
"""
import argparse
import json
import sys

from chipbench import manifest, run


def sustained(summary: dict, late_share: float) -> bool:
    return (summary['failed'] == 0 and summary['shed'] == 0
            and summary['queue_depth_last'] <= 2 * max(
                1.0, summary['queue_depth_mean'])
            and summary['p50_second_half_ms']
            <= 1.5 * summary['p50_first_half_ms']
            and summary['late_p50_ms']
            <= late_share * summary['mean_gap_ms'])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog='python3 -m chipbench.sweep',
                                     description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, default=1)
    parser.add_argument('--rates', required=True,
                        help='requests per second, comma-separated, rising')
    parser.add_argument('--seconds', type=float, default=8.0)
    parser.add_argument('--manifest', default=manifest.DEFAULT_MANIFEST)
    parser.add_argument('--rehearse-on-cpu', action='store_true')
    args = parser.parse_args(argv)
    started = run.start(args.workload, args.seed, False,
                        args.rehearse_on_cpu, args.manifest)
    if started is None:
        return 3
    ctx, runner = started.ctx, started.runner
    runner.setup()
    try:
        runner.warm()
        for i, rate in enumerate(float(r) for r in args.rates.split(',')):
            params = run.merged(ctx.traffic['arrivals'],
                                {'rate_per_s': rate})
            compiles = started.compiles.value
            offered = runner.offer(params, args.seed + i, args.seconds)
            summary = runner.summarize(offered, args.seconds)
            summary.update(rate_per_s=rate,
                           sustained=sustained(summary, float(
                               ctx.traffic['max_late_p50_share_of_gap'])),
                           compiles=started.compiles.value - compiles)
            print(json.dumps(summary), flush=True)
    finally:
        runner.close()
    return 0


if __name__ == '__main__':
    sys.exit(main())
