"""Global RNG: paddle.seed / Generator parity over jax threaded PRNG keys.

Reference parity: paddle/fluid/framework/generator.cc, pybind/generator_py.cc.
TPU-native: a Generator holds a jax PRNG key; every draw splits it. Inside a
jit trace the key must be an explicit input — `split_for_trace` hands out a
key that is deterministic per trace-site so eager and traced paths agree; the
train-step compiler threads a live key through state (see framework/functional).
"""
import contextlib

import jax
import numpy as np


class Generator:
    def __init__(self, seed=0):
        self._seed = int(seed)
        self._key_val = None   # lazy: creating a PRNGKey initializes the
        self._trace_counter = 0  # XLA backend, which must not happen at
        # import time (it would break jax.distributed.initialize in
        # multi-process children, and take the chip in any process that
        # merely imports the framework)

    @property
    def _key(self):
        if self._key_val is None:
            self._key_val = jax.random.PRNGKey(self._seed)
        return self._key_val

    @_key.setter
    def _key(self, value):
        self._key_val = value

    def manual_seed(self, seed):
        self._seed = int(seed)
        self._key = jax.random.PRNGKey(self._seed)
        self._trace_counter = 0
        return self

    def seed(self):  # paddle Generator.initial_seed-ish
        return self._seed

    def split(self):
        """Return a fresh key, advancing internal state (eager path)."""
        self._key, sub = jax.random.split(self._key)
        return sub

    def get_state(self):
        return np.asarray(self._key)

    def set_state(self, state):
        self._key = jax.numpy.asarray(state, dtype=jax.numpy.uint32)


_DEFAULT = Generator(0)


def default_generator():
    return _DEFAULT


def seed(s):
    """paddle.seed parity: reseed the global generator."""
    _DEFAULT.manual_seed(s)
    return _DEFAULT


def get_rng_state():
    return _DEFAULT.get_state()


def set_rng_state(state):
    _DEFAULT.set_state(state)


def next_key():
    return _DEFAULT.split()


@contextlib.contextmanager
def key_scope(key):
    """Temporarily seat `key` (possibly a tracer) as the generator state.

    The schedule engines (pipeline GPipe/1F1B scan bodies, sp attention)
    use this to hand model code a key derived from (step key, microbatch
    index, stage, layer) — so dropout masks drawn inside a traced-once
    scan body differ per tick/microbatch and reproduce exactly when the
    1F1B backward recomputes a stage (reference capability:
    fleet/meta_parallel/parallel_layers/random.py RNGStatesTracker).
    """
    gen = _DEFAULT
    saved = gen._key
    gen._key = key
    try:
        yield gen
    finally:
        gen._key = saved
