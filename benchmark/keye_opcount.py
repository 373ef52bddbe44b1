"""Operations a causal decoder of routed experts needs whose attention reads
the ``topk`` keys a learned index picks, from its configuration's shapes
alone (the source's ``config.json`` keys).  As ``opcount.py``: a multiply-add
is two operations, the backward pass costs twice the forward, nothing
recomputed, nothing padded and nothing masked away is counted: attention is
over the pairs the selection holds, whatever a kernel computes to get them,
so that a later kernel that skips work reads a larger share of the same
count and none can read over 100%.
"""

from sdar_opcount import layer_matmul_params


def selected_pairs(seq, topk):
    """Query-key pairs of one causal sequence that a selection of ``topk``
    keys a query holds: ``sum_t min(t + 1, topk)``."""
    k = min(seq, topk)
    return k * (k + 1) // 2 + (seq - k) * k


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def index_params(cfg):
    """Weights one position meets in the index's three projections."""
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def keye_train_flops_per_item(cfg, traffic):
    """Forward and backward of one token at ``seq_len`` tokens a sequence: 6
    a matrix weight it meets (the layer's as ``sdar_opcount`` counts them,
    the index's three projections, the head); attention's two products over
    the selected pairs, ``2 x 2 x heads x head_dim`` a pair; the index
    scores over the causal pairs, ``2 x index heads x (index head_dim + 1)``
    a pair (the products and the weighted sum), forward and backward (the
    KL term differentiates them).  The main attention's probabilities that
    the KL term reads are a recomputation and are not counted."""
    s, sa = traffic["seq_len"], cfg["sa_config"]
    layers = cfg["num_hidden_layers"]
    weights = layers * (layer_matmul_params(cfg) + index_params(cfg)) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    attn_fwd = layers * selected_pairs(s, sa["topk"]) / s \
        * 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    index_fwd = layers * causal_pairs(s) / s * 2 * sa["indexer_num_heads"] \
        * (sa["indexer_head_dim"] + 1)
    return 6 * weights + 3 * (attn_fwd + index_fwd)


def _sel_flash(products, arrays, batch, heads, seq, topk, head_dim,
               itemsize):
    ops = batch * heads * selected_pairs(seq, topk) * products * 2 * head_dim
    nbytes = batch * heads * seq * (arrays * head_dim * itemsize + 4) \
        + batch * seq * seq // 8
    return ops, nbytes


def sel_flash_forward_ops_bytes(batch, heads, seq, topk, head_dim, itemsize):
    """The flash forward over the selected pairs of ``batch`` causal
    sequences: its two products over them; q, k, v read and the output
    written once plus one float32 log-sum-exp a row (each key-value head
    counted once for every query head it serves: the kernel reads it so),
    and the selection's bitmap once."""
    return _sel_flash(2, 4, batch, heads, seq, topk, head_dim, itemsize)


def sel_flash_backward_ops_bytes(batch, heads, seq, topk, head_dim,
                                 itemsize):
    """The flash backward over the same pairs: five products; q, k, v, the
    output and its gradient read and dq, dk, dv written once, the
    log-sum-exp and the bitmap."""
    return _sel_flash(5, 8, batch, heads, seq, topk, head_dim, itemsize)
