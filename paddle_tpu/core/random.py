"""RNG state.

The reference keeps a per-device Philox generator registry
(paddle/phi/core/generator.cc) seeded by `paddle.seed`. JAX RNG is
functional, so the framework keeps one host-side splitting generator: every
random op draws a fresh subkey at *wrapper* level (not inside the traced
impl) so recomputation/replay of an op never re-samples.
"""
from __future__ import annotations

import threading

import jax
import numpy as np

_DEFAULT_SEED = 34342423252


class Generator:
    """The key is built on first use, not at construction: building it
    initialises the JAX backend, and ``import paddle_tpu`` (which makes
    ``default_generator``) must leave the accelerator unclaimed so a
    launcher parent can hand it to its worker."""

    def __init__(self, seed: int | None = None):
        self._lock = threading.Lock()
        self.manual_seed(seed if seed is not None else _DEFAULT_SEED)

    def manual_seed(self, seed: int):
        with self._lock:
            self._seed = int(seed)
            self._key = None
        return self

    seed = manual_seed

    def initial_seed(self) -> int:
        return self._seed

    def _ensure_key(self):
        # caller holds self._lock
        if self._key is None:
            self._key = jax.random.key(self._seed % (2**63))
        return self._key

    def split_key(self):
        """Return a fresh subkey, advancing the generator state."""
        with self._lock:
            self._key, sub = jax.random.split(self._ensure_key())
            return sub

    def get_state(self):
        with self._lock:
            return jax.random.key_data(self._ensure_key())

    def set_state(self, state):
        with self._lock:
            self._key = jax.random.wrap_key_data(np.asarray(state))


default_generator = Generator()


def seed(s: int) -> Generator:
    """paddle.seed analogue: reseed the global generator."""
    return default_generator.manual_seed(s)


def get_rng_state():
    return default_generator.get_state()


def set_rng_state(state):
    default_generator.set_state(state)


def split_key():
    return default_generator.split_key()
