"""Model FLOPs of one training step of a decoder-only transformer.

6 FLOPs per matmul parameter per token (forward 2, backward 4), over every
matmul weight: the attention and MLP projections of each layer and the LM
head (the tied embedding counts once, as the head).  An untied input
embedding is a gather and counts nothing; norms and biases count nothing.
Attention adds 12 * layers * (heads * head_dim) * seq per token for the
score and value products, forward and backward, over the full (S x S)
score matrix as the PaLM paper counts it.  Recomputed work (remat) does
not count.

Sizes are read from a configuration file of this benchmark (Hugging Face
key names), never from the program.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    d = c["hidden_size"]
    f = c["intermediate_size"]
    h = c["num_attention_heads"]
    kv = c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def train_step_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one step over ``batch`` rows of ``seq`` tokens."""
    tokens = batch * seq
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    attn = 12 * c["num_hidden_layers"] * h * hd * seq
    return float(6 * matmul_params(c) * tokens + attn * tokens)
