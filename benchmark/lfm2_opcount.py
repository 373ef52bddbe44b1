"""Operations a causal decoder needs whose layers are gated short
convolutions or grouped-query attention, with leading dense layers and routed
experts chosen under a selection bias, from its configuration's shapes alone
(the source's ``config.json`` keys).  As ``opcount.py``: a multiply-add is two
operations, the backward pass costs twice the forward, nothing recomputed,
nothing padded and nothing masked away is counted: attention is over the
causal pairs, and the experts over the even held load and not the buffer.
"""


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def mixer_params(cfg, kind):
    """Matrix weights one position meets in one layer's mixer: the conv
    mixer's two projections, or the attention layer's four."""
    d = cfg["hidden_size"]
    if kind == "conv":
        return d * 3 * d + d * d
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    return d * (d + 2 * kv) + d * d


def feed_forward_params(cfg, dense):
    """Weights one position meets after the mixer: the dense layer's three
    matrices, or the router at its published width and the experts this chip
    computes for it on average (``num_experts_per_tok`` of the published
    count are chosen, ``num_experts`` of them are held)."""
    d = cfg["hidden_size"]
    if dense:
        return 3 * d * cfg["intermediate_size"]
    routed = cfg["published"]["num_experts"]
    return d * routed + 3 * d * cfg["moe_intermediate_size"] \
        * cfg["num_experts_per_tok"] * cfg["num_experts"] / routed


def lfm2_train_flops_per_item(cfg, traffic):
    """Forward and backward of one token at ``seq_len`` tokens a sequence: 6
    a matrix weight it meets (each layer's mixer and feed-forward, the even
    held load and not the buffer, the tied head once: the table's other use
    is a lookup); in a conv layer the two gates and the ``conv_L_cache``
    taps, a multiply (and for the taps an add) a channel; in the attention
    layer the two products over the causal pairs, ``2 x 2 x heads x
    head_dim`` a pair."""
    s, d = traffic["seq_len"], cfg["hidden_size"]
    weights = d * cfg["vocab_size"]
    elementwise_fwd = attn_fwd = 0.0
    for i, kind in enumerate(cfg["layer_types"]):
        weights += mixer_params(cfg, kind) \
            + feed_forward_params(cfg, i < cfg["num_dense_layers"])
        if kind == "conv":
            elementwise_fwd += d * (2 + 2 * cfg["conv_L_cache"])
        else:
            attn_fwd += causal_pairs(s) / s * 4 * d
    return 6 * weights + 3 * (elementwise_fwd + attn_fwd)
