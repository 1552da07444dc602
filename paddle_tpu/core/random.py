"""Global RNG state.

Paddle has stateful global RNG (paddle.seed, reference:
python/paddle/framework/random.py); JAX is functional. Bridge: a global base
key + a fold-in counter. Every eager random op consumes ``next_key()``;
functional/compiled code paths should thread explicit keys instead
(``paddle_tpu.jit`` captures the counter as an input so compiled programs
stay pure).

The base key is materialized lazily: creating a ``jax.random.key`` touches the
JAX backend, and ``import paddle_tpu`` must never initialize a backend (a
process that has touched JAX holds the chip; an import must not decide
that).
"""
from __future__ import annotations

import threading


class _RNGState(threading.local):
    def __init__(self):
        self.seed = 0
        self.counter = 0
        self._key = None  # lazily created on first device touch
        self.capture_key = None  # set by paddle_tpu.jit during tracing

    @property
    def key(self):
        if self._key is None:
            import jax

            self._key = jax.random.key(self.seed)
        return self._key

    @key.setter
    def key(self, k):
        self._key = k


_state = _RNGState()


def seed(s: int):
    # No backend touch here: paddle.seed() at the top of a script is the
    # standard idiom and must not initialize JAX. The key re-derives lazily
    # from the stored seed on first random op.
    _state.seed = int(s)
    _state.counter = 0
    _state._key = None


def next_key():
    import jax

    if _state.capture_key is not None:
        # under program capture: derive from the traced key input so every
        # compiled invocation gets fresh randomness
        k = jax.random.fold_in(_state.capture_key, _state.counter)
    else:
        k = jax.random.fold_in(_state.key, _state.counter)
    _state.counter += 1
    return k


class capture_rng:
    """Context manager installing a traced base key during jit capture."""

    def __init__(self, key):
        self.key = key

    def __enter__(self):
        self._saved = (_state.capture_key, _state.counter)
        _state.capture_key = self.key
        _state.counter = 0
        return self

    def __exit__(self, *exc):
        _state.capture_key, _state.counter = self._saved
        return False


def get_rng_state():
    return (_state.seed, _state.counter)


def set_rng_state(st):
    _state.seed, _state.counter = st
    _state._key = None  # re-derive lazily from the restored seed


__all__ = ["seed", "next_key", "get_rng_state", "set_rng_state"]
