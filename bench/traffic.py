"""The traffic generator; a mix is a JSON file of parameters under
``bench/traffic/``.

``{"arrivals": "back_to_back"}``
    problems one after another, each with its own seed drawn from the run's,
    for as long as the window lasts.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int) -> np.random.Generator:
    """A generator for any whole-number seed, negative or beyond 64 bits."""
    s = int(seed) % (1 << 128)
    return np.random.default_rng([s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF,
                                  (s >> 64) & 0xFFFFFFFF, s >> 96])


def validate(mix: dict) -> None:
    """Refuse a mix the generator cannot make."""
    kind = mix.get("arrivals")
    if kind != "back_to_back":
        raise ValueError(f"unknown arrivals {kind!r}")
