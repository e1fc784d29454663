"""Offline step decomposition from a committed jax.profiler xplane trace.

VERDICT r3 #3 wanted the frozen-tables diag to isolate the scatter-add
share of the HBM gap; no chip run was possible then, but the round-2 trace
(`profiles/java14m_step/`) already carries per-op `hlo_category`,
`bytes_accessed`, and Python `source` attribution — enough to answer the
question offline. This tool aggregates the XLA-Ops line of the TPU plane
into ms/step by category, by originating source line, and by op, and
emits one JSON artifact.

Source lines refer to the file state at the commit that captured the
trace (8253ac4); the semantic mapping for the java14m step:
  functional.py:113/115/116 -> token/path/target-token gathers and their
                               backward scatter-adds
  functional.py:156         -> transform matmul (+tanh)
  functional.py:191         -> logits matmul (code @ target_emb.T)
  functional.py:214         -> logsumexp CE
  optax update.py:43        -> the dense Adam update walk

Run: python benchmarks/analyze_trace.py \
        [--trace profiles/java14m_step] [--steps 5] [--out ...]
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_xspace(trace_dir: str):
    """Newest capture under ``trace_dir`` (the timestamped dir names sort
    chronologically, so [-1] is the latest — [0] would silently pin the
    analysis to the OLDEST committed trace forever once a re-capture
    lands, e.g. the post-flip profile_v2 stage writing next to the
    round-2 trace)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not paths:
        raise FileNotFoundError('no *.xplane.pb under %s' % trace_dir)
    xs = xplane_pb2.XSpace()
    with open(paths[-1], 'rb') as f:
        xs.ParseFromString(f.read())
    return xs, paths[-1]


def decompose(xs, steps: int) -> dict:
    plane = next(pl for pl in xs.planes if pl.name.endswith('TPU:0'))
    smeta = {k: v.name for k, v in plane.stat_metadata.items()}
    emeta = dict(plane.event_metadata.items())

    def stats_of(md):
        out = {}
        for st in md.stats:
            name = smeta[st.metadata_id]
            out[name] = (st.str_value if st.str_value
                         else st.int64_value or st.uint64_value
                         or st.double_value)
        return out

    line = next(l for l in plane.lines if l.name == 'XLA Ops')
    by_cat = collections.Counter()
    by_cat_bytes = collections.Counter()
    by_src = collections.Counter()
    total_ps = 0
    for event in line.events:
        md = emeta[event.metadata_id]
        ms = stats_of(md)
        dur = 0
        for st in event.stats:
            if smeta[st.metadata_id] == 'device_duration_ps':
                dur = st.int64_value or st.uint64_value
        cat = ms.get('hlo_category', '?')
        by_cat[cat] += dur
        by_cat_bytes[cat] += int(ms.get('bytes_accessed', 0) or 0)
        src = str(ms.get('source', '?'))
        if src.startswith(REPO):
            src = src[len(REPO) + 1:]
        by_src[src] += dur
        total_ps += dur

    def ms_per_step(ps):
        return round(ps / 1e9 / steps, 3)

    return {
        'device_op_ms_per_step': ms_per_step(total_ps),
        'by_hlo_category': {
            cat: {'ms_per_step': ms_per_step(ps),
                  'gb_per_step': round(by_cat_bytes[cat] / steps / 1e9, 3)}
            for cat, ps in by_cat.most_common() if ps > 0},
        'by_source_line': {
            src: ms_per_step(ps)
            for src, ps in by_src.most_common(20) if ps > 0},
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--trace', default=os.path.join(
        REPO, 'profiles', 'java14m_step'))
    parser.add_argument('--steps', type=int, default=5,
                        help='train steps inside the trace bracket')
    parser.add_argument('--out', default=os.path.join(
        REPO, 'benchmarks', 'results', 'trace_breakdown_r4.json'))
    args = parser.parse_args()
    xs, path = load_xspace(args.trace)
    result = {
        'measure': 'trace_step_breakdown',
        'trace': os.path.relpath(path, REPO),
        'steps_in_bracket': args.steps,
        'source_line_note': ('source attribution refers to the file state '
                             'at the trace-capturing commit (8253ac4)'),
        **decompose(xs, args.steps),
    }
    print(json.dumps(result))
    with open(args.out, 'w') as f:
        json.dump(result, f, indent=1)


if __name__ == '__main__':
    main()
