"""A traffic mix's query stream, made from the seed.

The generator and its parameters come from the mix's file under
``traffic/``; this module turns them into the stream the program is
given (query ids, and the training statistics its cache is compiled
from) and the part of it that is served.

The mix fixes the stream itself (``stream_seed``): its training part,
and so the cache's layout and every shape the program compiles, are the
same for every run.  The run's ``--seed`` shuffles the served part
within consecutive blocks of ``shuffle_block`` requests: every seed
serves the same requests with the same popularity over time, in
another order.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import synth


@dataclasses.dataclass
class Stream:
    keys: np.ndarray  # the whole stream, training part first
    n_train: int
    key_topic: np.ndarray  # query id -> topic, NO_TOPIC for none
    synth: object  # the generator's output

    @property
    def served(self) -> np.ndarray:
        """The part after training: warm-up prefix, then the window."""
        return self.keys[self.n_train :]


def shuffle_blocks(keys: np.ndarray, block: int, seed) -> np.ndarray:
    """``keys`` shuffled within consecutive blocks of ``block``."""
    rng = np.random.default_rng(seed)
    n = len(keys) // block * block
    out = np.empty_like(keys)
    out[:n] = rng.permuted(keys[:n].reshape(-1, block), axis=1).ravel()
    out[n:] = rng.permuted(keys[n:])
    return out


def make(traffic: dict, seed: int, topic_pipeline=None) -> Stream:
    """The mix's stream, its served part in the order ``seed`` draws."""
    s = generate(traffic, int(traffic["stream_seed"]), topic_pipeline)
    keys = s.keys.copy()
    keys[s.n_train :] = shuffle_blocks(s.served, int(traffic["shuffle_block"]), seed)
    return dataclasses.replace(s, keys=keys)


def generate(traffic: dict, seed: int, topic_pipeline=None) -> Stream:
    """Generate the mix's stream from ``seed``.

    ``topic_pipeline(synth_log, **params)`` is the program's topic
    discovery, used by the calibrated generator: it returns the query
    -> topic table the cache is built on, as a deployment's classifier
    would.  The drift generator carries its own topics."""
    gen = traffic["generator"]
    p = dict(traffic["params"])
    if gen == "drift":
        cfg = synth.DriftConfig(seed=seed, **p)
        log = synth.generate_drifting(cfg)
        n_train = len(log.keys) * int(traffic["train_phases"]) // cfg.n_phases
        return Stream(log.keys, n_train, log.true_topic, log)
    if gen == "calibrated":
        cfg = synth.SynthConfig(seed=seed, **p)
        log = synth.generate(cfg)
        if topic_pipeline is None:
            raise ValueError("the calibrated generator needs the topic pipeline")
        key_topic, n_train = topic_pipeline(log, **traffic["topics"])
        return Stream(log.keys, n_train, key_topic, log)
    raise ValueError(f"unknown generator {gen!r}")
