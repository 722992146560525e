"""The one traffic generator: a closed loop of batches, drawn from a
traffic file's parameters and the seed.

A traffic file gives ``batches``, a list of ``{"B", "S", "count"}`` (or
``"tokens"`` in place of ``"B"``: B = tokens / S), and ``gen``, the tokens
generated per request.  One cycle holds every batch ``count`` times; each
seed plays the same cycles in its own order, so every seed sends the same
sizes and the seed changes only their order and the prompt tokens.
"""
from __future__ import annotations

import functools
import random

import jax
import jax.numpy as jnp


def shapes(traffic: dict) -> list[tuple[int, int]]:
    """The distinct (B, S) of the mix, in the file's order."""
    out = []
    for b in traffic["batches"]:
        B = b["B"] if "B" in b else b["tokens"] // b["S"]
        out.append((B, b["S"]))
    return out


def schedule(traffic: dict, seed: int, n: int) -> list[tuple[int, int]]:
    """The first ``n`` batches (B, S) of the seed's closed loop."""
    rng = random.Random(f"traffic:{seed}")
    cycle = [bs for bs, b in zip(shapes(traffic), traffic["batches"])
             for _ in range(b["count"])]
    out = []
    while len(out) < n:
        rng.shuffle(cycle)
        out.extend(cycle)
    return out[:n]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _prompt_block(key, n, B, S, vocab):
    return jax.random.randint(key, (n, B, S), 0, vocab, jnp.int32)


def cycle_len(traffic: dict) -> int:
    return sum(b["count"] for b in traffic["batches"])


def seed_key(seed: int, salt: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    key = jax.random.PRNGKey(salt)
    for part in (seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF, seed >> 62):
        key = jax.random.fold_in(key, part)
    return key


def prompt_pool(traffic: dict, seed: int, vocab: int) -> list:
    """Prompts for ``pool_cycles`` whole cycles of ``schedule``, made on
    the device, one program per shape; batch ``i`` of the window gets
    ``pool[i % len(pool)]``."""
    n = traffic["pool_cycles"] * cycle_len(traffic)
    sched = schedule(traffic, seed, n)
    key = seed_key(seed, 1)
    pool = [None] * n
    for j, (B, S) in enumerate(shapes(traffic)):
        idx = [i for i, bs in enumerate(sched) if bs == (B, S)]
        if not idx:
            continue
        block = _prompt_block(jax.random.fold_in(key, j), len(idx), B, S,
                              vocab)
        for r, i in enumerate(idx):
            pool[i] = block[r]
    return pool
