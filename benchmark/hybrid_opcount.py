"""Operations a decoder of state-space and attention layers needs, from its
configuration's shapes alone (the source's ``config.json`` keys).  As
``opcount.py``: a multiply-add is two operations, the backward pass costs
twice the forward, nothing recomputed is counted.
"""


def hybrid_matmul_params(cfg):
    """Weights that sit in a matrix product for every token: each layer's
    mixer and gated feed-forward, and the tied table once, as the head (the
    embedding look-up is no product)."""
    d, ff = cfg["hidden_size"], cfg["shared_intermediate_size"]
    d_inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    conv_dim = d_inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    hd = d // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    mixer = {"mamba": d * (d_inner + conv_dim + cfg["mamba_n_heads"])
             + d_inner * d,
             "attention": d * (q + 2 * kv) + q * d}
    return sum(mixer[kind] + 3 * d * ff for kind in cfg["layer_types"]) \
        + d * cfg["vocab_size"]


def scan_forward_ops_per_token(cfg):
    """The recurrence of one state-space layer, per token: every head's
    ``P x N`` state takes one multiply-add (``dt x B^T`` into the decayed
    state) and gives one (``S C``)."""
    return 2 * 2 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
        * cfg["mamba_d_state"]


def hybrid_train_flops_per_item(cfg, traffic):
    """Forward and backward of one token at sequence length ``seq_len``: 6 a
    matrix weight; each attention layer's two products (scores, weighted
    sum), 2*S*heads*head_dim each per token when full and half of that under
    the mask; each state-space layer's recurrence.  The convolution, the
    norms and the gates are not matrix work."""
    s = traffic["seq_len"]
    kinds = cfg["layer_types"]
    attn_fwd = kinds.count("attention") * 2 * (2 * s * cfg["hidden_size"]) // 2
    scan_fwd = kinds.count("mamba") * scan_forward_ops_per_token(cfg)
    return 6 * hybrid_matmul_params(cfg) + 3 * (attn_fwd + scan_fwd)
