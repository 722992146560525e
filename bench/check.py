"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and holding the one with the longest context, goes through
the configuration's plain float32 reference: each prompt with the tokens
the program served.  At each position whose logits the program produced
(the prefill's last position and every decode step), the gap read is how
far the reference's logit of the program's token lies below the
reference's best (0 where the program picked the best).  Two numbers come
of the gaps over the sample, and a cell's limits file names those it
compares:

* ``gap``: the widest gap;
* ``mean_gap``: the mean gap per position, which sums every pick below
  the best and so swings less from seed to seed than the widest one.

A served token outside the vocabulary (a padded row of the table), or a
gap that is not finite, is counted apart as ``unservable``: any at all
makes the run incorrect.

The control (``quant=True``) puts the reference in the program's place at
a lower precision (fp8 weights and activations): at the same positions it
reads the gap of the token the control puts first.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import random

import jax
import jax.numpy as jnp
import numpy as np

#: tokens in one reference call (bounds its attention scores' memory)
CHUNK_TOKENS = 8192


@dataclasses.dataclass
class Request:
    prompt: np.ndarray       # (S,)
    served: np.ndarray       # (gen,) the tokens the program returned
    last: int                # the program's pick from its last logits


def reference(spec: dict):
    return importlib.import_module(f"bench.reference.{spec['family']}")


def sample(requests: list[Request], seed: int, n: int) -> list[Request]:
    """``n`` finished requests drawn from the seed, the one with the
    longest context among them."""
    rng = random.Random(f"check:{seed}")
    longest = max(len(r.prompt) for r in requests)
    first = rng.choice([i for i, r in enumerate(requests)
                        if len(r.prompt) == longest])
    rest = [i for i in range(len(requests)) if i != first]
    picked = [first] + rng.sample(rest, min(n - 1, len(rest)))
    return [requests[i] for i in picked]


@functools.partial(jax.jit, static_argnums=(0, 2, 4, 5))
def _gaps(ref_logits, params, spec, tokens, n_out, quant):
    """Per position: reference best minus the reference's logit of the
    program's token (``tokens[:, -n_out:]`` shifted: the token chosen at a
    position is the next input), and the same for the control's pick."""
    ref = ref_logits(params, spec, tokens[:, :-1], n_out, False)
    best = ref.max(-1)
    chosen = tokens[:, -n_out:]
    outside = (chosen < 0) | (chosen >= ref.shape[-1])
    got = jnp.take_along_axis(ref, chosen[..., None], -1, mode="clip")[..., 0]
    out = jnp.where(outside, jnp.inf, best - got)
    if quant:
        ctl = ref_logits(params, spec, tokens[:, :-1], n_out, True)
        pick = jnp.argmax(ctl, -1)
        out = best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return out


def compare(params, spec: dict, reqs: list[Request], n: int, *,
            quant: bool = False) -> dict:
    """``gap`` (widest finite gap), ``mean_gap`` (finite gaps summed over
    the positions read), ``positions`` and ``unservable`` over ``reqs``, a
    sample of ``n``; requests of one length go through the reference
    together, in chunks of a shape fixed by the length and ``n`` (padded
    with copies)."""
    fn = reference(spec).logits
    by_len: dict[int, list[Request]] = {}
    for r in reqs:
        by_len.setdefault(len(r.prompt), []).append(r)
    worst, total, count, unservable = 0.0, 0.0, 0, 0
    for S, group in sorted(by_len.items()):
        gen = len(group[0].served)
        rows = np.stack([np.concatenate([r.prompt, r.served, [r.last]])
                         for r in group]).astype(np.int32)
        per = max(1, min(n, CHUNK_TOKENS // (S + gen)))
        for i in range(0, len(rows), per):
            chunk = rows[i:i + per]
            real = len(chunk)
            if real < per:
                chunk = np.concatenate([chunk, np.repeat(chunk[:1],
                                                         per - real, 0)])
            g = _gaps(fn, params, spec, jnp.asarray(chunk), gen + 1, quant)
            g = np.asarray(g[:real])
            finite = np.isfinite(g)
            unservable += int((~finite).sum())
            if finite.any():
                worst = max(worst, float(g[finite].max()))
                total += float(g[finite].sum())
            count += real * (gen + 1)
    return {"gap": worst, "mean_gap": total / count, "positions": count,
            "unservable": unservable}
