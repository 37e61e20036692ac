"""``reference_walk.greedy_by_reference``: the walk pads every call to ONE
length behind what is there, reads a position's logits where the sequence
ends, and refuses a near-tie."""

import numpy as np
import pytest

from . import reference_walk

VOCAB = 7


def counting_forward(lengths):
    """A causal 'model': position t's best token is (sum of tokens up to t) mod
    VOCAB, by a margin of 1; pads (token 0) behind a position change nothing."""
    def logits_of(tokens):
        lengths.append(len(tokens))
        best = np.cumsum(tokens) % VOCAB
        return np.eye(VOCAB, dtype=np.float32)[best]
    return logits_of


def test_every_call_has_the_longest_sequences_length_and_the_tokens_are_the_unpadded_ones():
    lengths = []
    requests = [[1, 2, 3], [5], [2, 2, 2, 2, 2]]
    got = reference_walk.greedy_by_reference(counting_forward(lengths), requests, 3)
    assert set(lengths) == {5 + 3} and len(lengths) == 3 * 3
    want = []
    for p in requests:
        tokens = list(p)
        for _ in range(3):
            tokens.append(sum(tokens) % VOCAB)
        want.append(tokens[len(p):])
    assert got == want


def test_a_runner_up_inside_the_margin_is_refused():
    def near_tie(tokens):
        logits = np.zeros((len(tokens), VOCAB), np.float32)
        logits[:, 3], logits[:, 4] = 1.0, 1.0 - 1e-4
        return logits

    with pytest.raises(AssertionError):
        reference_walk.greedy_by_reference(near_tie, [[1, 2]], 2)
    assert reference_walk.greedy_by_reference(
        near_tie, [[1, 2]], 2, least_margin=1e-5) == [[3, 3]]
