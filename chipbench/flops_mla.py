"""Operations and bytes of a DeepSeek-V2 configuration (latent attention,
a leading dense layer, DeepSeekMoE with a held share of the routed
experts), computed from shapes alone, as ``flops.py`` does for the others.

"Useful" FLOPs: 2 per multiply-add; prefill in the expanded form (per-head
k_nope and v made from the latent, causal scores and weighted values at half
the square); decode in the absorbed form (q_nope into the latent, scores and
the weighted sum over the cached latent and rotary key, then Wkv_b's v half);
routed experts at the chip's expected share, top-k x held / routed per token;
the shared experts and the dense layer in full; the output head only where
logits are made.  Norms, softmax and rotary terms are left out.
"""
from __future__ import annotations


def _dims(c: dict) -> tuple:
    return (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])


def param_count(c: dict) -> int:
    """Parameters held on the chip, norms included."""
    d, h, r, dn, dr, dv = _dims(c)
    attn = d * h * (dn + dr) + d * (r + dr) + r + r * h * (dn + dv) \
        + h * dv * d
    dense = c["first_k_dense_replace"]
    moe_layers = c["num_hidden_layers"] - dense
    ff, fe = c["intermediate_size"], c["moe_intermediate_size"]
    moe = (c["n_routed_experts"] + c["n_shared_experts"]) * 3 * d * fe \
        + d * c["router_experts"]
    return (2 * c["vocab_size"] * d + d
            + c["num_hidden_layers"] * (attn + 2 * d)
            + dense * 3 * d * ff + moe_layers * moe)


def _keys(q_from: int, q_to: int) -> int:
    """Keys seen by queries at positions [q_from, q_to), each against itself
    and every earlier position."""
    return sum(range(q_from + 1, q_to + 1))


def mla_prefill_flops(c: dict, tokens: int) -> int:
    """Latent attention of a prefill of ``tokens`` positions, every layer:
    the q, kv_a, kv_b and output projections, causal scores and values."""
    d, h, r, dn, dr, dv = _dims(c)
    proj = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    attn = h * (dn + dr + dv) * _keys(0, tokens)
    return 2 * c["num_hidden_layers"] * (tokens * proj + attn)


def mla_decode_flops(c: dict, position: int) -> int:
    """Latent attention of one decoded token at ``position`` (absorbed):
    q, kv_a, q_nope into the latent, scores against the cached latent and
    rotary key, the weighted latent, Wkv_b's v half and Wo, every layer."""
    d, h, r, dn, dr, dv = _dims(c)
    keys = position + 1
    per_layer = (d * h * (dn + dr) + d * (r + dr) + h * dn * r
                 + h * (r + dr) * keys + h * r * keys + h * r * dv
                 + h * dv * d)
    return 2 * c["num_hidden_layers"] * per_layer


def mla_decode_bytes(c: dict, batch: int, position: int,
                     bytes_per_el: int = 2) -> int:
    """Bytes one decode step's latent attention must move at least, every
    layer: its weights once, and each row's cached latent and rotary key
    (``position`` tokens)."""
    d, h, r, dn, dr, dv = _dims(c)
    weights = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) \
        + h * dv * d
    return bytes_per_el * c["num_hidden_layers"] * (
        weights + batch * position * (r + dr))


def _ffn_flops_per_token(c: dict) -> float:
    """Dense layer, router, routed experts at the held share and shared
    experts, summed over the layers, per token."""
    d = c["hidden_size"]
    dense = c["first_k_dense_replace"]
    fe = c["moe_intermediate_size"]
    routed = c["num_experts_per_tok"] * c["n_routed_experts"] \
        / c["router_experts"]
    moe = d * c["router_experts"] \
        + (routed + c["n_shared_experts"]) * 3 * d * fe
    return 2 * (dense * 3 * d * c["intermediate_size"]
                + (c["num_hidden_layers"] - dense) * moe)


def head_flops(c: dict) -> int:
    return 2 * c["hidden_size"] * c["vocab_size"]


def request_flops(c: dict, batch: int, prompt: int, new_tokens: int) -> float:
    """Useful FLOPs of one served request: prefill of ``prompt`` tokens per
    row with logits at its last position, then ``new_tokens - 1`` decode
    steps, each against the cache so far."""
    ffn, head = _ffn_flops_per_token(c), head_flops(c)
    per_row = mla_prefill_flops(c, prompt) + prompt * ffn + head
    for pos in range(prompt, prompt + new_tokens - 1):
        per_row += mla_decode_flops(c, pos) + ffn + head
    return batch * per_row


def mla_least_s(c: dict, batch: int, prompt: int, new_tokens: int,
                flops_per_s: float, bytes_per_s: float) -> float:
    """The least time a request's latent attention could take on a chip of
    those peaks: its prefill at the compute bound, and each decode step at
    the larger of its compute and memory bounds."""
    t = batch * mla_prefill_flops(c, prompt) / flops_per_s
    for pos in range(prompt, prompt + new_tokens - 1):
        t += max(batch * mla_decode_flops(c, pos) / flops_per_s,
                 mla_decode_bytes(c, batch, pos) / bytes_per_s)
    return t
