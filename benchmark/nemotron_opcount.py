"""Operations a decoder needs whose blocks are each one part alone (a
Mamba-2 mixer, grouped-query attention, or routed squared-ReLU experts that
work in a latent beside a shared expert), every part the share of its heads
or experts that one chip holds, from its configuration's shapes alone (the
source's ``config.json`` keys, whose counts are the held ones).  As
``opcount.py``: a multiply-add is two operations, the backward pass costs
twice the forward, nothing recomputed, nothing padded and nothing masked away
is counted: attention is over the causal pairs, and the experts over the even
held load and not the buffer.
"""


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def part_params(cfg, kind):
    """Matrix weights one position meets in one block of ``kind`` (a letter
    of ``hybrid_override_pattern``).  ``M``: ``in_proj`` over the held heads'
    ``[z | x | B | C | dt]`` columns and ``out_proj`` over their rows.
    ``*``: the four projections of the held query and key-value heads.
    ``E``: the router at its published width, the two latent projections,
    the shared expert's two matrices, and the experts this chip computes for
    the position on average (``num_experts_per_tok`` of the published count
    are chosen, ``n_routed_experts`` of them are held), two matrices each at
    the latent width."""
    d = cfg["hidden_size"]
    if kind == "M":
        h, g = cfg["mamba_num_heads"], cfg["n_groups"]
        d_inner = h * cfg["mamba_head_dim"]
        return d * (2 * d_inner + 2 * g * cfg["ssm_state_size"] + h) \
            + d_inner * d
    if kind == "*":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        return d * (q + 2 * kv) + q * d
    if kind != "E":
        raise ValueError(f"no part {kind!r}")
    routed = cfg["published"]["n_routed_experts"]
    latent = cfg["moe_latent_size"]
    return d * routed + 2 * d * latent \
        + 2 * d * cfg["moe_shared_expert_intermediate_size"] \
        + 2 * latent * cfg["moe_intermediate_size"] \
        * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / routed


def scan_forward_ops_per_token(cfg):
    """The recurrence of one Mamba-2 block, per token: every held head's
    ``P x N`` state takes one multiply-add (``dt x B^T`` into the decayed
    state) and gives one (``s C``)."""
    return 2 * 2 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]


def nemotron_train_flops_per_item(cfg, traffic):
    """Forward and backward of one token at ``seq_len`` tokens a sequence: 6
    a matrix weight it meets (each block's part, the untied head; the
    table's use is a lookup); in the attention block the two products over
    the causal pairs, ``2 x 2 x held heads x head_dim`` a pair; in each
    Mamba-2 block the recurrence.  The convolution, the norms, the gates and
    the squared ReLU are not matrix work."""
    s = traffic["seq_len"]
    pattern = cfg["hybrid_override_pattern"]
    weights = cfg["hidden_size"] * cfg["vocab_size"] \
        + sum(part_params(cfg, kind) for kind in pattern)
    attn_fwd = pattern.count("*") * causal_pairs(s) / s \
        * 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    scan_fwd = pattern.count("M") * scan_forward_ops_per_token(cfg)
    return 6 * weights + 3 * (attn_fwd + scan_fwd)

