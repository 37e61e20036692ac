"""The default generator of serving traffic: parameters in, requests out.
(A traffic file that names a ``"generator"`` gets ``generators/<name>.py``
instead, with this signature and this ``Request``: ``benchmark/cells.py``.)

A traffic file of kind ``serve`` gives a Poisson arrival rate and two length
distributions; this turns them into a list of requests, a pure function of
``(parameters, seed, seconds, vocab)``. Copied in spirit from
``scaling_tpu/serve/bench.py`` ``sample_workload`` (Poisson offsets, random
token ids) with what that one measures wrongly repaired: lengths are drawn
from heavy-tailed distributions and not uniformly, the window opens on a
system already in its steady state, and the schedule (when each request is
due, how long its prompt and its answer are) is the SAME for every seed:
``shape_seed`` in the traffic file draws it, ``--seed`` draws the token ids.
A window holds some tens of chat requests, and with so few the order in which
long and short ones arrive decides who queues behind whom: on the chip, the
same lengths and gaps in another order moved the 95th percentile of time to
first token between 3.4 s and 13 s while two runs of one order agreed to 1.7%
(PERF.md, Findings PR 24). So the schedule is replayed, as a fixed trace is:
a cell's values are those of this one trace, and a difference between two
runs is the system's and not the draw's.

Parameters: ``rate`` (requests/s, Poisson), ``prompt`` and ``output``
(``{"median", "sigma", "min", "max"}``, lognormal, clipped), ``max_total``
(prompt + output, the engine's context), ``shape_seed``, ``warm_seconds``
(real traffic at ``rate`` before the window, not counted) and ``history``:
``{"seconds", "tick_s", "prefill_tokens_per_tick"}``. A request lives for
tens of seconds, longer than a run may spend before its window, so the state
the window opens on is drawn and not played: arrivals at ``rate`` over
``seconds`` of virtual history before the warm-up, each aged by a nominal
engine (a tick every ``tick_s``; a prompt streams in at
``prefill_tokens_per_tick``, then one output token a tick; both are the
figures of the sweep that fixed ``rate``). A request that would have finished
is gone; one still running is submitted as the warm-up starts, its prompt
longer by the tokens it had generated and its answer shorter by as many, so
its context and its remaining work are those of a request in mid-life. The
warm-up then gives those prompts time to stream in.

Two further keys are read by ``serve_kind.py`` and not here: ``"backlog"``
(``"fail"``, the default, or ``"cut"``: how the window ends) and ``"tokens"``
(``"all"``, the default, or ``"counted"``: whether ``serve_tokens_per_s``
counts every output token stamped inside the window or those of the counted
requests alone; traffic below the knee, as this generator's, wants
``"counted"``: ``serve_kind.TOKEN_RULES`` says why).
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float          # offset from the window's start; < 0 is warm-up
    prompt: List[int]
    output_len: int
    # of the traced part that follows the window (--trace 2): due_s is
    # then an offset from when arrivals resume, and it is never counted
    traced: bool = False

    @property
    def counted(self) -> bool:
        return self.due_s >= 0.0 and not self.traced


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def _shapes(rng, traffic: dict, span_s: float):
    """Poisson arrivals over ``span_s`` seconds, the first at its start:
    (offsets from the start, prompt lengths, output lengths)."""
    offsets, t = [], 0.0
    while t < span_s:
        offsets.append(t)
        t += float(rng.exponential(1.0 / float(traffic["rate"])))
    prompts = _lengths(rng, traffic["prompt"], len(offsets))
    outputs = _lengths(rng, traffic["output"], len(offsets))
    outputs = np.minimum(outputs, int(traffic["max_total"]) - prompts)
    return np.asarray(offsets), prompts, outputs


def _aged(history: dict, age_s: float, prompt: int, output: int):
    """What is left, after ``age_s`` seconds in the nominal engine, of a
    request of ``prompt`` + ``output`` tokens: (prompt, output) as it is
    submitted, or nothing if it has finished. The tick that streams in the
    last of the prompt yields the first output token."""
    ticks = int(age_s / float(history["tick_s"]))
    prefill_ticks = -(-prompt // int(history["prefill_tokens_per_tick"]))
    generated = max(0, ticks - prefill_ticks + 1)
    if generated >= output:
        return None
    return prompt + generated, output - generated


def generate(traffic: dict, seed: int, seconds: float, vocab: int,
             traced_seconds: float = 0.0) -> List[Request]:
    """The requests still running out of the virtual history, due as the
    warm-up starts (``-warm_seconds``); the warm-up's, due in
    ``[-warm_seconds, 0)``; and the window's, due in ``[0, seconds)``; in
    order of due time. With ``traced_seconds`` (--trace 2) the arrivals of
    the traced part follow them, marked ``traced`` and due in
    ``[0, traced_seconds)`` after arrivals resume: a further phase with a
    generator of its own, its token ids drawn last, so that everything
    before it is the same request for request and token for token."""
    phase_rng = lambda k: np.random.default_rng([int(traffic["shape_seed"]), k])
    warm = float(traffic["warm_seconds"])
    history = traffic["history"]
    span = float(history["seconds"])
    shapes = []  # (due_s, prompt tokens, output tokens)
    for off, p, o in zip(*_shapes(phase_rng(0), traffic, span)):
        left = _aged(history, span - off, int(p), int(o))
        if left is not None:
            shapes.append((-warm, *left))
    for k, start, span in ((1, -warm, warm), (2, 0.0, float(seconds))):
        shapes += [(start + off, int(p), int(o))
                   for off, p, o in zip(*_shapes(phase_rng(k), traffic, span))]
    traced_from = len(shapes)
    if traced_seconds > 0:
        shapes += [(off, int(p), int(o)) for off, p, o in
                   zip(*_shapes(phase_rng(3), traffic, float(traced_seconds)))]
    # the seed's part: the token ids
    token_rng = np.random.default_rng(seed)
    return [Request(due_s=float(due),
                    prompt=token_rng.integers(1, vocab, size=p).tolist(),
                    output_len=o, traced=i >= traced_from)
            for i, (due, p, o) in enumerate(shapes)]
