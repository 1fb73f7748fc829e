"""Record kind ``token_chain``: a vectorised copy of
``synthetic.gen_sequence`` — a fixed permutation Markov chain over
``alphabet`` tokens, each step replaced by a uniform token with probability
``noise``; ``seq_len + 1`` int64 tokens a record, as the LM zoo's
``dataset_fn`` expects."""

from __future__ import annotations

import numpy as np

# gen_sequence's chain comes from a fixed RNG, so every seed draws from one
# underlying distribution
_FIXED_RNG = 1234


def token_chain(rng, count: int, seq_len: int, alphabet: int, noise: float):
    """``(count, seq_len + 1)`` int64 tokens."""
    perm = np.random.RandomState(_FIXED_RNG).permutation(alphabet)
    tokens = np.empty((count, seq_len + 1), np.int64)
    tokens[:, 0] = rng.integers(alphabet, size=count)
    flip = rng.random((count, seq_len)) < noise
    uniform = rng.integers(alphabet, size=(count, seq_len))
    for t in range(1, seq_len + 1):
        tokens[:, t] = np.where(
            flip[:, t - 1], uniform[:, t - 1], perm[tokens[:, t - 1]]
        )
    return tokens


def shared_state(spec: dict):
    """Nothing is shared between shards."""
    return None


def columns(rng, spec: dict, count: int, state=None) -> dict:
    """``count`` records, one array a field of the record on disk."""
    return {
        "tokens": token_chain(
            rng, count, int(spec["seq_len"]), int(spec["alphabet"]),
            float(spec["noise"]),
        )
    }


def batch(columns: dict):
    """``(features, labels)`` as the zoo's parse function hands them to
    the trainer."""
    tokens = columns["tokens"].astype(np.int32)
    return {"tokens": tokens[:, :-1]}, tokens[:, 1:]


def batch_shapes(spec: dict, rows: int):
    """``(features, labels)`` as ``(shape, dtype)`` pairs, for a compile
    without data."""
    tokens = ((rows, int(spec["seq_len"])), "int32")
    return {"tokens": tokens}, tokens


def units(spec: dict) -> dict:
    """How many of each work unit one record is."""
    return {"tokens": int(spec["seq_len"]), "records": 1}
