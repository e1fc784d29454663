"""Turns of long-lived sessions over a repository snapshot: the same
sessions, the same lengths, the same order and the same arrival instants in
every run.  ``--seed`` decides the token ids (of the sessions' contexts and
of every turn's prompt) and nothing else, as in ``ide_replay.py``, whose
quantile and ordering helpers this reuses.

``sessions.count`` sessions are resident from set-up; session ``s`` starts
at the ``s``-th quantile of the ``sessions`` lognormal (the runner prefills
it through the engine's own ``generate`` path before the window).  A cycle
of ``turns.count`` turn templates, repeated: the appended prompt lengths are
the quantiles of the ``turns`` lognormal in bit-reversed order, every turn
generates ``turns.new_tokens`` tokens to the end, and turn ``i`` goes to
session ``i mod sessions.count``, so a session is asked for a turn once
every ``sessions.count / rate_per_s`` seconds.  One turn falls due every
``1 / rate_per_s`` seconds exactly, from ``lead_in_s`` before the window.

Parameters (``traffic/<mix>.json``, key ``arrivals``)::

    rate_per_s   turns per second
    lead_in_s    seconds of the schedule before the window
    sessions     {count, median, sigma, min, max}: context at set-up, tokens
    turns        {count, median, sigma, min, max, new_tokens}: appended prompt

``generate(params, seed, seconds, n_lines)`` has the generators' common
signature; ``n_lines`` is the vocabulary the ids are drawn from.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from chipbench.traffic.ide_replay import bit_reversed, quantiles


class Turns(NamedTuple):
    due_s: np.ndarray         # (n,) float64 from the window's start; < 0
    #                           in the lead-in
    prompt_len: np.ndarray    # (n,) int64 tokens appended by the turn
    new_tokens: np.ndarray    # (n,) int64, always generated to the end
    session: np.ndarray       # (n,) int64 the session the turn belongs to
    template: np.ndarray      # (n,) int64 place in the cycle
    session_len: np.ndarray   # (sessions,) int64 context at set-up
    seed: int
    vocab: int


def cycle(params: dict) -> np.ndarray:
    """The appended prompt lengths of one cycle's templates, in order."""
    lengths = quantiles(params['turns'])
    return lengths[bit_reversed(lengths.shape[0])]


def generate(params: dict, seed: int, seconds: float, n_lines: int) -> Turns:
    prompt_len = cycle(params)
    session_len = quantiles(params['sessions'])
    gap = 1.0 / float(params['rate_per_s'])
    lead = int(np.floor(float(params['lead_in_s']) / gap))
    n = lead + int(np.ceil(seconds / gap - 1e-9))
    turn = np.arange(n)
    template = turn % prompt_len.shape[0]
    return Turns(due_s=(turn - lead) * gap, prompt_len=prompt_len[template],
                 new_tokens=np.full(n, int(params['turns']['new_tokens']),
                                    np.int64),
                 session=turn % session_len.shape[0], template=template,
                 session_len=session_len, seed=int(seed), vocab=int(n_lines))


def session_ids(turns: Turns, s: int) -> np.ndarray:
    """Session ``s``'s context at set-up: seeded ids, uniform over the
    vocabulary, a stream of its own a session."""
    rng = np.random.default_rng([turns.seed, 0x5E5, int(s)])
    return rng.integers(0, turns.vocab, int(turns.session_len[s]),
                        dtype=np.int32)


def prompt_ids(turns: Turns, i: int) -> np.ndarray:
    """Turn ``i``'s appended prompt: a stream of its own a turn."""
    rng = np.random.default_rng([turns.seed, 0x7A9, int(i)])
    return rng.integers(0, turns.vocab, int(turns.prompt_len[i]),
                        dtype=np.int32)
