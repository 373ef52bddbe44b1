"""Operations and bytes the algorithms need, from shapes alone.

These are the numerators of every utilization and roofline share.  They count
what the mathematics requires (a multiply-add is two operations; the backward
pass costs twice the forward; nothing recomputed is counted), not what XLA
executes.
"""


def _out(size, stride):
    return -(-size // stride)


def resnet_forward_macs(cfg):
    """Multiply-adds of one image's forward pass through the convolutions
    and the classifier (batch norm, ReLU and pooling are not matrix work)."""
    h = w = None
    h, w, c = cfg["image_shape"]
    stem = cfg["stem_width"]
    h, w = _out(h, 2), _out(w, 2)                 # 7x7 stride 2
    macs = h * w * 7 * 7 * c * stem
    h, w = _out(h, 2), _out(w, 2)                 # 3x3 max pool stride 2
    c_in = stem
    for s, (width, blocks) in enumerate(zip(cfg["stage_widths"],
                                            cfg["stage_blocks"])):
        c_out = width * cfg["expansion"]
        for i in range(blocks):
            stride = 2 if (i == 0 and s > 0) else 1
            macs += h * w * c_in * width          # 1x1 at the input size
            ho, wo = _out(h, stride), _out(w, stride)
            macs += ho * wo * 9 * width * width   # 3x3 carries the stride
            macs += ho * wo * width * c_out       # 1x1
            if i == 0:
                macs += ho * wo * c_in * c_out    # projected shortcut
            h, w, c_in = ho, wo, c_out
    return macs + c_in * cfg["num_classes"]


def resnet_train_flops_per_item(cfg, traffic):
    """Forward and backward of one image: 3 x forward, 2 per multiply-add."""
    return 6 * resnet_forward_macs(cfg)


def gpt2_matmul_params(cfg):
    """Weights that sit in a matrix product for every token: the blocks and
    the head (this system's head is a matrix of its own).  Embedding look-ups
    are not products."""
    d, ff = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (3 * d * d + d * d + 2 * d * ff) \
        + d * cfg["vocab_size"]


def gpt2_train_flops_per_item(cfg, traffic):
    """Forward and backward of one token at sequence length ``seq_len``: 6
    per weight, and causal attention's two products (scores, weighted sum),
    each 2*S*d per token and layer when full, half of that under the mask."""
    s, d = traffic["seq_len"], cfg["n_embd"]
    attn_fwd = cfg["n_layer"] * 2 * (2 * s * d) // 2
    return 6 * gpt2_matmul_params(cfg) + 3 * attn_fwd


def flash_forward_ops_bytes(batch, heads, seq, head_dim, itemsize):
    """Causal attention forward for (batch, seq, heads, head_dim): the two
    products over the lower triangle, and q, k, v read and the output
    written once plus one float32 log-sum-exp per row."""
    ops = batch * heads * 2 * (2 * seq * seq * head_dim) // 2
    nbytes = batch * heads * seq * (4 * head_dim * itemsize + 4)
    return ops, nbytes
