"""Operations and bytes that the algorithm needs, counted from the
published sizes in a configuration file.  Nothing here reads the
program: a change to how the program computes cannot move these counts.

Conventions:
* a multiply-add is 2 operations; a token's embedding gather is none;
* attention counts ``head_dim`` as published (64), not a padded width;
  causal prefill counts half of the score matrix; decode counts K and V
  once per KV head, up to the keys that exist (``kv_len``);
* a routed-expert layer counts its top-k experts only;
* the vocabulary head is counted where a logit is needed: the last prompt
  position in a prefill, every token in a decode step;
* bytes are those a kernel must move at the served dtype (bf16 = 2).
"""
from __future__ import annotations

BF16 = 2


def _attn_dims(spec):
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    return d, H, spec["num_key_value_heads"], d // H


def dense_flops_per_token(spec: dict) -> int:
    """One token through every layer's projections, router and routed
    experts (attention scores excluded: they depend on the context)."""
    d, H, Hkv, D = _attn_dims(spec)
    attn = 2 * d * (2 * H * D + 2 * Hkv * D)                 # q, k, v, o
    router = 2 * d * spec["num_local_experts"]
    experts = (2 * 3 * d * spec["intermediate_size"]
               * spec["num_experts_per_tok"])                # gate, up, down
    return spec["num_hidden_layers"] * (attn + router + experts)


def head_flops(spec: dict) -> int:
    return 2 * spec["hidden_size"] * spec["vocab_size"]


def attention_flops(spec: dict, queries: int, kv_len: int,
                    causal: bool) -> int:
    """Scores and weighted sum of one sequence in one layer."""
    _, H, _, D = _attn_dims(spec)
    pairs = queries * kv_len
    if causal:
        pairs = queries * (queries + 1) // 2
    return 2 * 2 * H * D * pairs


def prefill_flops(spec: dict, B: int, S: int) -> int:
    """Model operations of one prefill call of B prompts of S tokens."""
    L = spec["num_hidden_layers"]
    return B * (S * dense_flops_per_token(spec)
                + L * attention_flops(spec, S, S, causal=True)
                + head_flops(spec))


def decode_flops(spec: dict, B: int, kv_len: int) -> int:
    """Model operations of one decode step of B sequences whose new token
    attends to ``kv_len`` keys (itself included)."""
    L = spec["num_hidden_layers"]
    return B * (dense_flops_per_token(spec)
                + L * attention_flops(spec, 1, kv_len, causal=False)
                + head_flops(spec))


def flash_attention_cost(spec: dict, B: int, S: int) -> tuple[int, int]:
    """(ops, bytes) of one causal prefill attention call (one layer):
    q and o per query head, k and v per KV head, bf16."""
    _, H, Hkv, D = _attn_dims(spec)
    ops = B * attention_flops(spec, S, S, causal=True)
    moved = B * S * D * (2 * H + 2 * Hkv) * BF16
    return ops, moved


def decode_attention_cost(spec: dict, B: int, kv_len: int
                          ) -> tuple[int, int]:
    """(ops, bytes) of one decode attention call (one layer): K and V
    read once per KV head up to ``kv_len``, q and o of one token."""
    _, H, Hkv, D = _attn_dims(spec)
    ops = B * attention_flops(spec, 1, kv_len, causal=False)
    moved = B * (2 * kv_len * Hkv * D + 2 * H * D) * BF16
    return ops, moved


def least_time(ops: int, moved: int, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["bf16_flops"]
    t_mem = moved / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
