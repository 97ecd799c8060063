"""Seeded deployment worlds for a text-classification fleet.

The task shape of FedNLP's 20Newsgroups split (arXiv:2104.08815): 20
classes, a few documents per client, one majority class per client.
Data are synthetic, class-conditioned token sequences over the model's
whole vocabulary, made in numpy on the host from ``--seed`` with no
import of the system under test: each class has its own set of topic
tokens, and each position of a sequence of the class is one of them
with probability ``topic_frac``, else any token of the vocabulary.
Partition, data sizes, fleet and padding are ``world.py``'s.
"""
from __future__ import annotations

import numpy as np

from bench import world as wd


def sequences(rng, topics, n, seq_len, vocab, topic_frac):
    """(X (n, seq_len) int32, y (n,) int32) drawn from the class topics."""
    n_classes, topic_size = topics.shape
    y = rng.integers(0, n_classes, n)
    pick = topics[y[:, None], rng.integers(0, topic_size, (n, seq_len))]
    anywhere = rng.integers(0, vocab, (n, seq_len))
    X = np.where(rng.random((n, seq_len)) < topic_frac, pick, anywhere)
    return X.astype(np.int32), y.astype(np.int32)


def build_world(config, seed):
    """The deployment of ``config`` drawn from run seed ``seed``: as
    ``world.build_world``, with token sequences in place of images."""
    data, system = config["data"], config["system"]
    N, vocab = system["n_devices"], config["vocab_size"]
    rng = np.random.default_rng(wd.sub_seed(seed, 1))
    topics = rng.permutation(vocab)[:data["n_classes"] * data["topic_size"]]
    topics = topics.reshape(data["n_classes"], data["topic_size"])
    X, y = sequences(rng, topics, data["n_train"], data["seq_len"], vocab,
                     data["topic_frac"])
    X_test, y_test = sequences(rng, topics, data["n_test"], data["seq_len"],
                               vocab, data["topic_frac"])
    sizes = wd.device_sizes(N, system["d_range"], wd.sub_seed(seed, 2))
    Xs, ys, majority = wd.partition(X, y, sizes, data["n_classes"],
                                    wd.sub_seed(seed, 3))
    Xp, yp, mask = wd.pad(Xs, ys, int(system["d_range"][1]))
    return wd.World(wd.fleet(system, sizes, wd.sub_seed(seed, 4)), Xs, ys,
                    majority, X_test, y_test, Xp.astype(np.int32), yp, mask)
