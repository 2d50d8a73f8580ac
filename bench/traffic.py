"""The general traffic generator: a mix's data file in, a ring of host
batches out.

A mix (``bench/traffic/<name>.json``) states the global batch, how many
microbatches each chip splits its share into, the sequence length where the
inputs are tokens, the strategy of the exchange, and how many distinct
batches the ring holds. The configuration says what an input is (images or
tokens) and its sizes (image size and classes, or vocabulary). The ring is
drawn from the seed on the device in one call and copied to the host once;
the window then cycles it through the program's own input path.
"""
from __future__ import annotations

import itertools
import json
from pathlib import Path

KINDS = ("images", "tokens")
# the first steps, whose results are checked, each need a batch of its own
MIN_RING = 3


def load(path: str | Path) -> dict:
    mix = json.loads(Path(path).read_text())
    for key in ("global_batch", "microbatches", "ring", "strategy"):
        if key not in mix:
            raise KeyError(f"{path}: traffic mix lacks {key!r}")
    if mix["ring"] < MIN_RING:
        raise ValueError(f"{path}: ring {mix['ring']} < {MIN_RING}")
    return mix


def seed_key(seed: int, stream: int):
    """A key for one use (``stream``) of a seed of any size."""
    import jax

    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(jax.random.fold_in(key, seed // 2**32), stream)


def make_ring(kind: str, sizes: dict, mix: dict, seed: int) -> list:
    """``mix["ring"]`` distinct host batches (dicts of numpy arrays)."""
    import jax
    import jax.numpy as jnp

    n, gb = mix["ring"], mix["global_batch"]
    if kind == "images":
        img, ncls = sizes["image_size"], sizes["n_classes"]

        def gen(key):
            ki, kl = jax.random.split(key)
            return {
                "images": jax.random.normal(ki, (n, gb, img, img, 3),
                                            jnp.float32),
                "labels": jax.random.randint(kl, (n, gb), 0, ncls, jnp.int32),
            }
    elif kind == "tokens":
        s, vocab = mix["seq_len"], sizes["vocab"]

        def gen(key):
            seq = jax.random.randint(key, (n, gb, s + 1), 0, vocab, jnp.int32)
            return {"tokens": seq[..., :-1], "labels": seq[..., 1:]}
    else:
        raise ValueError(f"input kind {kind!r} is not one of {KINDS}")
    host = jax.device_get(jax.jit(gen)(seed_key(seed, 1)))
    return [{k: v[i] for k, v in host.items()} for i in range(n)]


def cycle(ring: list):
    """Endless batches: the ring in order, again and again."""
    return itertools.cycle(ring)
