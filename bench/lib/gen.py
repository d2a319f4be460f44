"""Inputs made from the seed: images, client partitions and token
streams.

These are copies of the program's own generators, with the sizes made
parameters so the traffic files can set them:

- images: ``repro.data.synthetic.make_dataset``'s law (a prototype
  texture per class, a colour transform per domain, prototype jitter and
  pixel noise), at any image size, drawn on the device in one program;
- partition: ``repro.fl.partition.dirichlet_partition``'s law;
- tokens: ``repro.launch.train.synthetic_token_stream``'s law (each
  client favours its own half of the vocabulary, repeated bigrams), on
  the device.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, stream: int) -> jax.Array:
    """A JAX key for one named use of the run's seed (seeds may exceed
    32 bits)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 32), stream)


def np_rng(seed: int, stream: int) -> np.random.RandomState:
    s = (int(seed) * 1_000_003 + int(stream)) % (2 ** 32)
    return np.random.RandomState(s)


def labels_for(class_counts: Sequence[int], seed: int) -> np.ndarray:
    """Labels in a seeded random order, ``class_counts[c]`` of class c."""
    labels = np.concatenate([np.full(n, c, np.int32)
                             for c, n in enumerate(class_counts)])
    return labels[np_rng(seed, 1).permutation(len(labels))]


def _texture(k, size: int):
    """One random prototype texture (size, size, 3) in [-1, 1]."""
    g = jnp.linspace(-1.0, 1.0, size)
    xx, yy = jnp.meshgrid(g, g)
    u = jax.random.uniform(k, (7,))
    f1, f2 = 1 + 3 * u[0], 1 + 3 * u[1]
    ph0, ph1 = 2 * jnp.pi * u[2], 2 * jnp.pi * u[3]
    cx, cy = u[4] - 0.5, u[5] - 0.5
    w = 0.1 + 0.3 * u[6]
    base = jnp.sin(f1 * jnp.pi * xx + ph0) * jnp.cos(f2 * jnp.pi * yy + ph1)
    blob = jnp.exp(-((xx - cx) ** 2 + (yy - cy) ** 2) / w)
    p = jnp.stack([base, blob, base * blob], -1)
    return p / (jnp.abs(p).max() + 1e-6)


@partial(jax.jit, static_argnames=("size", "n_classes", "n_domains"))
def _images(k, labels, *, size: int, n_classes: int, n_domains: int):
    kp, kd, ks = jax.random.split(k, 3)
    protos = jax.vmap(lambda kk: _texture(kk, size))(
        jax.random.split(kp, n_classes))
    kmix, kbias = jax.random.split(kd)
    mix = jax.random.uniform(kmix, (n_domains, 3, 3), minval=-1, maxval=1)
    mix = mix / jnp.abs(mix).sum(-1, keepdims=True)
    bias = jax.random.uniform(kbias, (n_domains, 3), minval=-0.3,
                              maxval=0.3)

    def one(kk, c):
        k1, k2, k3, k4, k5 = jax.random.split(kk, 5)
        d = jax.random.randint(k1, (), 0, n_domains)
        img = protos[c] * jax.random.uniform(k2, (), minval=0.7, maxval=1.3)
        img = img + 0.25 * _texture(k3, size) * jax.random.normal(k4, ())
        img = jnp.einsum("hwc,cd->hwd", img, mix[d]) + bias[d]
        img = img + 0.15 * jax.random.normal(k5, img.shape)
        return jnp.clip(img, -1.0, 1.0)

    return jax.lax.map(lambda a: one(*a),
                       (jax.random.split(ks, labels.shape[0]), labels),
                       batch_size=64)


def images(seed: int, labels: np.ndarray, *, size: int, n_classes: int,
           n_domains: int) -> jax.Array:
    """(N, size, size, 3) float32 images on the device, in [-1, 1]."""
    return _images(key(seed, 2), jnp.asarray(labels, jnp.int32),
                   size=size, n_classes=n_classes, n_domains=n_domains)


def dirichlet_partition(labels: np.ndarray, n_clients: int, alpha: float,
                        seed: int) -> List[np.ndarray]:
    """Per-client sorted sample indices; each class is split over the
    clients by proportions drawn from Dir(alpha)."""
    rng = np_rng(seed, 3)
    n_classes = int(labels.max()) + 1
    by_class = [np.where(labels == c)[0] for c in range(n_classes)]
    for idx in by_class:
        rng.shuffle(idx)
    parts: List[list] = [[] for _ in range(n_clients)]
    for idx in by_class:
        if len(idx) == 0:
            continue
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props)[:-1] * len(idx)).astype(int)
        for i, part in enumerate(np.split(idx, cuts)):
            parts[i].extend(part.tolist())
    return [np.asarray(sorted(p), np.int64) for p in parts]


@partial(jax.jit, static_argnames=("vocab", "n_clients", "docs", "length"))
def _tokens(k, *, vocab: int, n_clients: int, docs: int, length: int):
    def client(kk, c):
        lo = (c * vocab) // (2 * n_clients)
        t = jax.random.randint(kk, (docs, length), lo, lo + vocab // 2)
        return t.at[:, 2::2].set(t[:, 1:-1:2])
    return jax.vmap(client)(jax.random.split(k, n_clients),
                            jnp.arange(n_clients))


def token_stream(seed: int, *, vocab: int, n_clients: int, docs: int,
                 length: int) -> jax.Array:
    """(n_clients, docs, length) int32 documents on the device."""
    return _tokens(key(seed, 4), vocab=vocab, n_clients=n_clients,
                   docs=docs, length=length)


def class_prompts(n_classes: int) -> np.ndarray:
    """Class-prompt token ids ("a photo of a <class>"), the law of
    ``repro.data.synthetic.class_tokens``."""
    toks = np.tile(np.array([1, 2, 3, 4, 0, 0, 0, 0], np.int32),
                   (n_classes, 1))
    toks[:, 4] = 10 + np.arange(n_classes)
    toks[:, 5] = 5
    return toks


def round_keys(seed: int, n: int) -> np.ndarray:
    """(n, 2) raw keys, one per federated round, on the host; key r does
    not depend on n."""
    return np.asarray(jax.vmap(lambda r: jax.random.fold_in(
        key(seed, 12), r))(jnp.arange(n)))
