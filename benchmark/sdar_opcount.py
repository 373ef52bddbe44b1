"""Operations a decoder of rotary attention and routed experts, trained by
diffusion over blocks, needs, from its configuration's shapes alone (the
source's ``config.json`` keys).  As ``opcount.py``: a multiply-add is two
operations, the backward pass costs twice the forward, nothing recomputed
and nothing padded is counted.
"""


def mask_pairs(length, block):
    """Query-key pairs of one sequence that the block-diffusion mask over
    ``[noisy ; clean]`` (``2 length`` positions, blocks of ``block``)
    allows: the noisy half's block diagonal (``length x block``), the
    clean keys of earlier blocks for noisy queries and of blocks up to
    their own for clean ones (``block^2 x n (n - 1) / 2`` and ``block^2 x
    n (n + 1) / 2``, ``n`` blocks): ``length x (length + block)`` of the
    ``4 length^2`` square."""
    n = length // block
    return length * block + block * block * (n * (n - 1) // 2
                                             + n * (n + 1) // 2)


def layer_matmul_params(cfg):
    """Weights one position meets in a matrix product in one layer: the
    four projections, the router at its published width, and the experts
    this chip computes for it on average: ``num_experts_per_tok`` of the
    published count are chosen, ``num_experts`` of them are held."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    routed = cfg["published"]["num_experts"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    return d * (q + 2 * kv) + q * d + d * routed \
        + expert * cfg["num_experts_per_tok"] * cfg["num_experts"] / routed


def sdar_train_flops_per_item(cfg, traffic):
    """Forward and backward of one token of data at ``seq_len`` tokens a
    sequence: the token is two positions (noisy and clean) in every layer, 6
    a matrix weight each; the head once (over the noisy half); attention's
    two products (scores, weighted sum) over the pairs the mask allows,
    ``2 x 2 x heads x head_dim`` a pair."""
    s = traffic["seq_len"]
    weights = 2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    pairs_per_token = mask_pairs(s, traffic["block_length"]) / s
    attn_fwd = cfg["num_hidden_layers"] * pairs_per_token \
        * 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return 6 * weights + 3 * attn_fwd


def _bd_flash(products, arrays, batch, heads, seq, block, head_dim,
              itemsize):
    ops = batch * heads * mask_pairs(seq, block) * products * 2 * head_dim
    nbytes = batch * heads * 2 * seq * (arrays * head_dim * itemsize + 4)
    return ops, nbytes


def bd_flash_forward_ops_bytes(batch, heads, seq, block, head_dim, itemsize):
    """The flash forward under the block-diffusion mask for ``batch``
    sequences of ``2 seq`` positions: its two products over the pairs the
    mask allows; q, k, v read and the output written once plus one float32
    log-sum-exp a row (each key-value head counted once for every query
    head it serves: the kernel reads it so)."""
    return _bd_flash(2, 4, batch, heads, seq, block, head_dim, itemsize)


def bd_flash_backward_ops_bytes(batch, heads, seq, block, head_dim, itemsize):
    """The flash backward under the same mask: five products over the same
    pairs; q, k, v, the output and its gradient read and dq, dk, dv written
    once, plus the log-sum-exp."""
    return _bd_flash(5, 8, batch, heads, seq, block, head_dim, itemsize)


def grouped_ops_bytes(rows, d_in, d_out, groups, itemsize):
    """One grouped product over a buffer of ``rows`` rows: every row times
    its group's ``d_in x d_out`` matrix; the buffer read, the ``groups``
    matrices read once, the result written in float32."""
    return 2 * rows * d_in * d_out, \
        rows * d_in * itemsize + groups * d_in * d_out * itemsize \
        + rows * d_out * 4
