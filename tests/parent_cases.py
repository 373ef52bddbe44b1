"""The third routed configuration's mechanisms at toy size (layers that
differ: windowed and full attention with their own head counts and rotary
rules, a gate a head, a dense layer, sigmoid scores, a scale, a shared
expert): the arguments ``tests/fixtures/routed_lm_parent43.npz`` was made with
from the parent commit's ``RoutedLM`` (PR 42's tree, ``git archive`` of it),
and ``tests/test_short_conv_lm.py`` builds again from this tree's.  The two
other routed configurations' are ``tests/test_mixed_attention.py``'s
``PARENT_CASES``, held to PR 40's tree."""

_ROPES = {
    "full": {"rope_theta": 500000.0, "rotary_dim": 8, "yarn": {
        "factor": 8.0, "original_max_position_embeddings": 32,
        "beta_fast": 4, "beta_slow": 1,
        "attention_factor": 1.2079441541679836}},
    "window": {"rope_theta": 10000.0}}

#: case -> (RoutedLM's arguments, the tokens' shape)
PARENT_CASES = {
    "mixed_layers": (dict(
        vocab_size=40, embed_dim=32, num_layers=3, num_heads=4,
        num_kv_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate=24, held_experts=(2, 4), buffer_rows=256,
        objective="causal", attention=None, window=20,
        dense_intermediate=48, attn_gate=True, qk_norm=False,
        scoring="sigmoid", routed_scale=2.5, shared_intermediate=24,
        layers=(
            {"attention": "full", "num_heads": 4, "rope": _ROPES["full"],
             "mlp": "dense"},
            {"attention": "window", "num_heads": 6, "rope": _ROPES["window"]},
            {"attention": "full", "num_heads": 4, "rope": _ROPES["full"]})),
        (2, 64)),
}
