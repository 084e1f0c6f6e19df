"""Useful operations of a dense decoder, from the configuration's shapes, and
the table of device peaks.

Counted: every matrix product of the projections, the MLP and the LM head
(2 operations per multiply-add) and the two attention products (scores and
the weighted sum of values) over the real context.  Not counted: padding,
norms, rotary embeddings, softmax, biases, and the LM head at prompt
positions whose logits nobody reads.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The peak figures of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer that take part in a matrix product."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def attention_flops(c: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, all
    layers: 2 products x 2 operations x heads x head size x context."""
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    return 4 * c["num_hidden_layers"] * h * hd * context


def decode_flops(c: dict, context: int) -> int:
    """One generated token whose query attends ``context`` keys (itself
    included): every layer's products, the LM head, attention."""
    mm = c["num_hidden_layers"] * layer_matmul_params(c) + head_params(c)
    return 2 * mm + attention_flops(c, context)


def prefill_flops(c: dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` real tokens: every layer at every position,
    causal attention (position p attends p + 1 keys), LM head once."""
    p = prompt_len
    layers = c["num_hidden_layers"] * layer_matmul_params(c)
    attn = attention_flops(c, 1) * p * (p + 1) // 2
    return 2 * layers * p + 2 * head_params(c) + attn
