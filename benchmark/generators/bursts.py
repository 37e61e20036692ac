"""Generator ``bursts``: whole bursts of requests due at the same instant.

A batch job, an evaluation harness or a fleet of clients that reconnects
drops ``burst_size`` prompts on a chat deployment at once, again and again:
every ``burst_every_s`` seconds from the window's start (t = 0, every, 2 x
every, ... while t < seconds), after one uncounted burst ``warm_seconds``
before it, so that the window opens on slots already full and a queue. No
virtual history: a burst fills the engine by itself. Offered faster than the
engine drains, the queue is the traffic's design (``"backlog": "cut"`` in the
traffic file).

A pure function of ``(parameters, seed, seconds, vocab)``, with the signature
and the ``Request`` of ``benchmark/traffic_gen.py``. As there, the schedule
(due times, the lengths and their order inside a burst: the queue is first in,
first out) is the SAME for every seed: ``shape_seed`` draws it, burst by burst,
so a shorter window is a prefix of a longer one; ``--seed`` draws the token
ids. Parameters: ``burst_size``, ``burst_every_s``, ``warm_seconds``,
``shape_seed``, and ``prompt``, ``output`` (``{"median", "sigma", "min",
"max"}``, lognormal, clipped) and ``max_total`` as ``traffic_gen`` reads them.
With ``traced_seconds`` (--trace 2) the bursts of the traced part follow,
marked ``traced``, the first due as arrivals resume.
"""

from __future__ import annotations

from typing import List

import numpy as np

from benchmark.traffic_gen import Request, _lengths


def _bursts(rng, traffic: dict, start_s: float, span_s: float):
    """(due, prompt length, output length) of every request of the bursts at
    ``start_s``, ``start_s + every``, ... before ``start_s + span_s``."""
    size, every = int(traffic["burst_size"]), float(traffic["burst_every_s"])
    shapes, k = [], 0
    while k * every < span_s:
        prompts = _lengths(rng, traffic["prompt"], size)
        outputs = np.minimum(_lengths(rng, traffic["output"], size),
                             int(traffic["max_total"]) - prompts)
        shapes += [(start_s + k * every, int(p), int(o))
                   for p, o in zip(prompts, outputs)]
        k += 1
    return shapes


def generate(traffic: dict, seed: int, seconds: float, vocab: int,
             traced_seconds: float = 0.0) -> List[Request]:
    phase_rng = lambda k: np.random.default_rng([int(traffic["shape_seed"]), k])
    warm = float(traffic["warm_seconds"])
    every = float(traffic["burst_every_s"])
    shapes = []
    if warm > 0:  # ONE burst before the window, whatever the spacing
        shapes += _bursts(phase_rng(1), traffic, -warm, min(warm, every))
    shapes += _bursts(phase_rng(2), traffic, 0.0, float(seconds))
    traced_from = len(shapes)
    if traced_seconds > 0:
        shapes += _bursts(phase_rng(3), traffic, 0.0, float(traced_seconds))
    # the seed's part: the token ids
    token_rng = np.random.default_rng(seed)
    return [Request(due_s=float(due),
                    prompt=token_rng.integers(1, vocab, size=p).tolist(),
                    output_len=o, traced=i >= traced_from)
            for i, (due, p, o) in enumerate(shapes)]
