"""Operations, bytes and parameters of the benchmark's programs, computed
from shapes alone (never from the program's own counters).

A configuration is the dict of its file under ``configs/`` (the published
``config.json`` keys).  "Useful" FLOPs count what the model needs and no
more: a matmul is 2 FLOPs per multiply-add, only the experts a token is
routed to, causal attention at half the square, and the output head only
where logits are produced.  Norms, softmax and rotary terms are left out.
"""
from __future__ import annotations


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def _attn_params(c: dict) -> int:
    d, hd = c["hidden_size"], head_dim(c)
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    qk_norm = 2 * hd if c.get("qk_norm") == "per_head" else 0
    return 2 * d * nq * hd + 2 * d * nkv * hd + qk_norm


def _mlp_params(c: dict, active: bool) -> int:
    d, ff = c["hidden_size"], c["intermediate_size"]
    experts = c.get("num_experts") or 0
    if not experts:
        return 3 * d * ff
    n = c["num_experts_per_tok"] if active else experts
    return n * 3 * d * ff + d * experts          # experts + router


def param_count(c: dict, active: bool = False) -> int:
    """Parameters held on the chip (``active``: those one token uses)."""
    d, v, n_layers = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    per_layer = _attn_params(c) + _mlp_params(c, active) + 2 * d   # 2 norms
    tables = v * d * (1 if c["tie_word_embeddings"] else 2)
    return tables + n_layers * per_layer + d                       # final norm


def weight_bytes(c: dict, bytes_per_param: int = 2) -> int:
    """Bytes of the served weights (the router is kept in float32)."""
    experts = c.get("num_experts") or 0
    router = c["num_hidden_layers"] * c["hidden_size"] * experts
    return param_count(c) * bytes_per_param + router * (4 - bytes_per_param)


def _matmul_flops_per_token(c: dict) -> int:
    """Layer matmuls one token needs (no output head)."""
    per_layer = _attn_params(c) + _mlp_params(c, active=True)
    if c.get("qk_norm") == "per_head":
        per_layer -= 2 * head_dim(c)
    return 2 * c["num_hidden_layers"] * per_layer


def _attn_flops(c: dict, q_pos_from: int, q_pos_to: int) -> int:
    """Scores and weighted values of queries at positions
    ``[q_pos_from, q_pos_to)``, each against itself and every earlier key."""
    keys = sum(range(q_pos_from + 1, q_pos_to + 1))
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * head_dim(c) * keys


def head_flops(c: dict) -> int:
    return 2 * c["hidden_size"] * c["vocab_size"]


def request_flops(c: dict, batch: int, prompt: int, new_tokens: int) -> int:
    """Useful FLOPs of one served request: prefill of ``prompt`` tokens per
    row with logits at its last position, then ``new_tokens - 1`` decode
    steps, each against the cache so far."""
    per_row = (prompt * _matmul_flops_per_token(c) + _attn_flops(c, 0, prompt)
               + head_flops(c))
    steps = new_tokens - 1
    per_row += steps * (_matmul_flops_per_token(c) + head_flops(c))
    per_row += _attn_flops(c, prompt, prompt + steps)
    return batch * per_row


def attention_block_flops(c: dict, batch: int, seq: int) -> int:
    """Useful FLOPs of one call of ``programs/attention_block``: causal
    scores and weighted values at half the square, and the output
    projection."""
    hd, nq, d = head_dim(c), c["num_attention_heads"], c["hidden_size"]
    attn = 4 * batch * nq * hd * seq * seq // 2
    return attn + 2 * batch * seq * nq * hd * d


def attention_block_bytes(c: dict, batch: int, seq: int,
                          bytes_per_el: int = 2) -> int:
    """Bytes one call must move at least: its inputs and its output."""
    hd, d = head_dim(c), c["hidden_size"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    q = batch * seq * nq * hd
    kv = 2 * batch * seq * nkv * hd
    x_out = 2 * batch * seq * d
    return bytes_per_el * (q + kv + x_out + nq * hd * d + d)
