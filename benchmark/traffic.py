"""The one general traffic generator and the feed the loop reads from.

A traffic mix is a data file ``traffic/<name>.json``: its parameters and the
function that draws one batch from them, named ``module:function`` (the two
here, or one in a module a later PR adds beside this file).  ``generate``
makes a cell's batches from the seed; every seed gives the same sizes.
``Feed`` hands them to ``Module.fit`` as the iterator a user would pass,
cycling.
"""

import importlib

import numpy as np


def uniform_images(rng, traffic, cfg):
    shape = (traffic["batch"],) + tuple(cfg["image_shape"])
    data = rng.random(shape, dtype=np.float32) * 2.0 - 1.0
    labels = rng.integers(0, cfg["num_classes"], traffic["batch"],
                          dtype=np.int32)
    return data, labels


def uniform_tokens(rng, traffic, cfg):
    toks = rng.integers(0, cfg["vocab_size"],
                        (traffic["batch"], traffic["seq_len"]),
                        dtype=np.int32)
    # the label of a position is the token that follows it
    return toks, np.roll(toks, -1, axis=1)


def generate(traffic, cfg, seed):
    """``distinct_batches`` batches of (data, labels) from ``seed``, each
    drawn by the mix's ``generator(rng, traffic, cfg)``."""
    rng = np.random.default_rng(int(seed))
    module, attr = traffic["generator"].split(":")
    make = getattr(importlib.import_module(module), attr)
    return [make(rng, traffic, cfg) for _ in range(traffic["distinct_batches"])]


class Feed:
    """The iterator ``fit`` reads: the generated batches in order, cycled,
    as the program's own ``DataBatch``.  One ``fit`` call is one epoch of
    ``limit`` steps, or runs until ``stop()`` where ``limit`` is None."""

    def __init__(self, batches, batch_cls, cast=None):
        self._batches = [batch_cls(d if cast is None else cast(d), lb, 0)
                         for d, lb in batches]
        self._cursor = 0      # position in the cycle, kept across epochs
        self._left = 0

    def arm(self, limit=None):
        self._left = float("inf") if limit is None else limit
        return self

    def stop(self):
        self._left = 0

    def reset(self):
        pass

    def next(self):
        if self._left <= 0:
            raise StopIteration
        self._left -= 1
        batch = self._batches[self._cursor % len(self._batches)]
        self._cursor += 1
        return batch
