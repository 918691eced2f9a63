"""The traffic generator: seeds of any size, and the mixes it accepts."""
import json
import os

import pytest

from bench import traffic


def test_same_seed_same_stream():
    for seed in (7, -3, 2**31 + 11, 2**70 + 5):
        assert (traffic.rng(seed).integers(0, 2**31, 8).tolist()
                == traffic.rng(seed).integers(0, 2**31, 8).tolist())


def test_seeds_beyond_32_bits_differ():
    a = traffic.rng(5).integers(0, 2**31, 8).tolist()
    assert a != traffic.rng(2**32 + 5).integers(0, 2**31, 8).tolist()
    assert a != traffic.rng(2**64 + 5).integers(0, 2**31, 8).tolist()


def test_committed_mixes_are_valid():
    d = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "traffic")
    for f in sorted(os.listdir(d)):
        with open(os.path.join(d, f)) as fh:
            traffic.validate(json.load(fh))


def test_bad_mixes_are_refused():
    for bad in ({"arrivals": "closed"}, {"arrivals": "poisson", "rate_per_s": 1.0}, {}):
        with pytest.raises(ValueError):
            traffic.validate(bad)
