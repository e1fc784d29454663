"""A replayed open-loop schedule of IDE requests to a code model: the same
lengths, the same order and the same arrival instants in every run.

With a few long requests a second, which long request lands beside which is
most of the median latency, so a schedule whose order and instants follow
the seed (``arrivals.py``) repeats to several percent only.  Here ``--seed``
decides the prompts' token ids (``prompt_ids``) and nothing else.

A cycle of request templates, repeated: ``complete`` (an inline completion
over the open file and retrieved snippets) and ``chat`` (an assistant or
edit turn over several files).  Each class's prompt lengths are the
quantiles of its lognormal, not draws; every ``chat_every``-th request is a
``chat``; inside each class the quantiles are visited in bit-reversed index
order, so that every stretch of the cycle holds short and long.  One request
falls due every ``1 / rate_per_s`` seconds exactly, from ``lead_in_s``
before the window (instants below zero: the window opens on an engine in
steady state) until its end.

Parameters (``traffic/<mix>.json``, key ``arrivals``)::

    rate_per_s   requests per second
    lead_in_s    seconds of the schedule before the window
    chat_every   every n-th request is a chat
    classes      {complete|chat: {count, median, sigma, min, max,
                 new_tokens}}: prompt lengths in tokens, lognormal

``generate(params, seed, seconds, n_lines)`` has the generators' common
signature; ``n_lines`` is the vocabulary the ids are drawn from.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import NamedTuple, Tuple

import numpy as np

KINDS = ('complete', 'chat')


class Replay(NamedTuple):
    due_s: np.ndarray         # (n,) float64 from the window's start; < 0
    #                           in the lead-in
    prompt_len: np.ndarray    # (n,) int64 tokens
    new_tokens: np.ndarray    # (n,) int64, always generated to the end
    kind: np.ndarray          # (n,) int64 index into ``kinds``
    template: np.ndarray      # (n,) int64 place in the cycle
    kinds: Tuple[str, ...]
    seed: int
    vocab: int


def quantiles(spec: dict) -> np.ndarray:
    """The class's ``count`` prompt lengths: its lognormal's quantiles."""
    n = int(spec['count'])
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.exp(np.log(spec['median']) + spec['sigma'] * z)
    return np.clip(np.rint(lengths), spec['min'],
                   spec['max']).astype(np.int64)


def bit_reversed(n: int) -> np.ndarray:
    """0..n-1 in bit-reversed order (of the next power of two, those below
    ``n`` kept)."""
    bits = max(1, int(np.ceil(np.log2(n))))
    order = [int(format(i, '0%db' % bits)[::-1], 2) for i in range(1 << bits)]
    return np.asarray([i for i in order if i < n], np.int64)


def cycle(params: dict):
    """(prompt_len, new_tokens, kind) of one cycle's templates, in order."""
    every = int(params['chat_every'])
    classes = params['classes']
    queues = {}
    for k, name in enumerate(KINDS):
        lengths = quantiles(classes[name])
        queues[k] = list(lengths[bit_reversed(lengths.shape[0])])
    total = sum(len(q) for q in queues.values())
    prompt_len, new_tokens, kind = [], [], []
    for i in range(total):
        k = 1 if (i % every == every - 1 and queues[1]) or not queues[0] \
            else 0
        prompt_len.append(queues[k].pop(0))
        new_tokens.append(int(classes[KINDS[k]]['new_tokens']))
        kind.append(k)
    return (np.asarray(prompt_len, np.int64),
            np.asarray(new_tokens, np.int64), np.asarray(kind, np.int64))


def generate(params: dict, seed: int, seconds: float, n_lines: int) -> Replay:
    prompt_len, new_tokens, kind = cycle(params)
    gap = 1.0 / float(params['rate_per_s'])
    lead = int(np.floor(float(params['lead_in_s']) / gap))
    n = lead + int(np.ceil(seconds / gap - 1e-9))
    due = (np.arange(n) - lead) * gap
    template = np.arange(n) % prompt_len.shape[0]
    return Replay(due_s=due, prompt_len=prompt_len[template],
                  new_tokens=new_tokens[template], kind=kind[template],
                  template=template, kinds=KINDS, seed=int(seed),
                  vocab=int(n_lines))


def prompt_ids(replay: Replay, i: int) -> np.ndarray:
    """Request ``i``'s prompt: seeded ids, uniform over the vocabulary, a
    stream of its own a request (no prefix is shared)."""
    rng = np.random.default_rng([replay.seed, 0x1DE, int(i)])
    return rng.integers(0, replay.vocab, int(replay.prompt_len[i]),
                        dtype=np.int32)
