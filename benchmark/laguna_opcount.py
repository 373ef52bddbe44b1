"""Operations a causal decoder needs whose layers alternate windowed and
full attention with different head counts, with a gate a head, a leading
dense layer and routed experts beside a shared one, from its configuration's
shapes alone (the source's ``config.json`` keys).  As ``opcount.py``: a
multiply-add is two operations, the backward pass costs twice the forward,
nothing recomputed, nothing padded and nothing masked away is counted:
windowed attention is over the pairs of the band, whatever tiles a kernel
runs to get them, so that a later kernel that skips more reads a larger
share of the same count and none can read over 100%.
"""


def band_pairs(seq, window):
    """Query-key pairs of one causal sequence under a window: ``sum_t min(t
    + 1, window)``."""
    w = min(seq, window)
    return w * (w + 1) // 2 + (seq - w) * w


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def layer_pairs(cfg, kind, seq):
    return band_pairs(seq, cfg["sliding_window"]) \
        if kind == "sliding_attention" else causal_pairs(seq)


def attention_params(cfg, heads):
    """Weights one position meets in one attention layer of ``heads`` query
    heads: the four projections and the gate's."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = heads * hd, cfg["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d + d * heads


def feed_forward_params(cfg, kind):
    """Weights one position meets after attention: the dense layer's three
    matrices, or the router at its published width, the shared expert, and
    the experts this chip computes for it on average
    (``num_experts_per_tok`` of the published count are chosen,
    ``num_experts`` of them are held)."""
    d = cfg["hidden_size"]
    if kind == "dense":
        return 3 * d * cfg["intermediate_size"]
    routed = cfg["published"]["num_experts"]
    return d * routed + 3 * d * cfg["shared_expert_intermediate_size"] \
        + 3 * d * cfg["moe_intermediate_size"] \
        * cfg["num_experts_per_tok"] * cfg["num_experts"] / routed


def laguna_train_flops_per_item(cfg, traffic):
    """Forward and backward of one token at ``seq_len`` tokens a sequence: 6
    a matrix weight it meets (each layer's own head count and feed-forward,
    the even held load and not the buffer, the head); attention's two
    products over the pairs the layer's rule allows (the band in a sliding
    layer, the triangle in a full one), ``2 x 2 x heads x head_dim`` a
    pair."""
    s = traffic["seq_len"]
    weights = cfg["hidden_size"] * cfg["vocab_size"]
    attn_fwd = 0.0
    for kind, heads, mlp in zip(cfg["layer_types"],
                                cfg["num_attention_heads_per_layer"],
                                cfg["mlp_layer_types"]):
        weights += attention_params(cfg, heads) + feed_forward_params(cfg, mlp)
        attn_fwd += layer_pairs(cfg, kind, s) / s * 4 * heads \
            * cfg["head_dim"]
    return 6 * weights + 3 * attn_fwd


def _win_flash(products, arrays, batch, heads, seq, window, head_dim,
               itemsize):
    ops = batch * heads * band_pairs(seq, window) * products * 2 * head_dim
    nbytes = batch * heads * seq * (arrays * head_dim * itemsize + 4)
    return ops, nbytes


def win_flash_forward_ops_bytes(batch, heads, seq, window, head_dim,
                                itemsize):
    """The flash forward under the band for ``batch`` sequences: its two
    products over the band's pairs; q, k, v read and the output written once
    plus one float32 log-sum-exp a row (each key-value head counted once for
    every query head it serves: the kernel reads it so)."""
    return _win_flash(2, 4, batch, heads, seq, window, head_dim, itemsize)


def win_flash_backward_ops_bytes(batch, heads, seq, window, head_dim,
                                 itemsize):
    """The flash backward under the band: five products over the same
    pairs; q, k, v, the output and its gradient read and dq, dk, dv written
    once, plus the log-sum-exp."""
    return _win_flash(5, 8, batch, heads, seq, window, head_dim, itemsize)
