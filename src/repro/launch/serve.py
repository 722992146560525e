"""Batched serving driver: prefill a batch of prompts, then decode with
the KV/state caches (greedy).  Reduced configs run real tokens on CPU; the
full configs drive the same path on a TPU, where ``lm.step`` runs the
Pallas kernels.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
      --batch 4 --prompt-len 32 --gen 16

``generate`` is the function behind the CLI: callers that hold the
parameters already (``chip_smoke.py``) call it directly.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp

from repro import compile_cache, configs
from repro.configs.base import ArchConfig
from repro.model import lm


@dataclasses.dataclass
class Generation:
    """One batch served: greedy tokens, the last-position logits of the
    prefill and of every decode step, and host-clock timings that each end
    in ``block_until_ready``."""
    #: (B, gen) greedy tokens; tokens[:, i] is the input of decode step i
    tokens: jax.Array
    #: gen + 1 arrays of (B, vocab_padded): prefill, then each decode step
    logits: list[jax.Array]
    compile_s: float
    prefill_s: float
    decode_s: float


def init_params(cfg: ArchConfig, seed: int = 0):
    """Seeded random parameters, built on the device in one program (an
    eager init would hold every float32 draw beside its bf16 copy)."""
    return jax.jit(functools.partial(lm.init_params, cfg))(
        jax.random.PRNGKey(seed))


def generate(params, cfg: ArchConfig, prompts: jax.Array, *, gen: int,
             extra=None) -> Generation:
    """Prefill ``prompts`` (B, S), then decode ``gen`` tokens greedily.
    The cache is donated to every step, and sampling stays on the device;
    the host waits only at the end of each timed phase."""
    B, S = prompts.shape
    cache = lm.init_cache(params, cfg, B, max_seq=S + gen, extra=extra)
    step = jax.jit(lambda p, c, t: lm.step(p, cfg, c, t), donate_argnums=(1,))
    t0 = time.perf_counter()
    prefill = step.lower(params, cache, prompts).compile()
    decode = step.lower(params, cache, prompts[:, :1]).compile()
    compile_s = time.perf_counter() - t0

    def greedy(logits):
        return jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    t0 = time.perf_counter()
    logits, cache = prefill(params, cache, prompts)
    tok = greedy(logits).block_until_ready()
    prefill_s = time.perf_counter() - t0

    out_logits, out_tokens = [logits], []
    t0 = time.perf_counter()
    for _ in range(gen):
        out_tokens.append(tok)
        logits, cache = decode(params, cache, tok)
        out_logits.append(logits)
        tok = greedy(logits)
    tok.block_until_ready()
    decode_s = time.perf_counter() - t0
    return Generation(tokens=jnp.concatenate(out_tokens, axis=1),
                      logits=out_logits, compile_s=compile_s,
                      prefill_s=prefill_s, decode_s=decode_s)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.configure()
    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get(args.arch)
    params = init_params(cfg, args.seed)
    B = args.batch
    extra = None
    if cfg.family == "vlm":
        extra = {"vision": jnp.ones((B, cfg.frontend_tokens,
                                     cfg.frontend_dim), jnp.bfloat16) * .01}
    elif cfg.family == "audio":
        extra = {"frames": jnp.ones((B, cfg.frontend_tokens,
                                     cfg.frontend_dim), jnp.bfloat16) * .01}
    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                 (B, args.prompt_len), 0, cfg.vocab)
    out = generate(params, cfg, prompts, gen=args.gen, extra=extra)
    print(f"compile {out.compile_s:.2f}s")
    print(f"prefill {args.prompt_len} tokens x {B}: {out.prefill_s:.3f}s")
    print(f"decoded {args.gen} x {B} tokens in {out.decode_s:.3f}s "
          f"({args.gen * B / out.decode_s:.1f} tok/s)")
    print("sample token ids:", out.tokens[0, :12].tolist())


if __name__ == "__main__":
    main()
