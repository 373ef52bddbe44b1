"""The job of a causal decoder of routed experts whose layers alternate
windowed and full attention with different head counts and rotary rules:
``models.RoutedLM(objective="causal", layers=...)`` through ``Module.fit`` on
one device.  Added beside ``drivers.py``, ``sdar_drivers.py`` and
``keye_drivers.py``; a configuration's file names it under ``driver``.
"""

from drivers import Job, LMJob, _dtype
from sdar_drivers import BlockDiffusionMoEJob

#: the source's names of the two kinds of attention layer -> the program's
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def layer_records(cfg):
    """One record a layer for ``RoutedLM.layers``, read from the
    configuration's three lists and its two ``rope_parameters``."""
    ropes = {}
    for kind, rope in cfg["rope_parameters"].items():
        if not isinstance(rope, dict):      # the group's own number
            continue
        mine = {"rope_theta": float(rope["rope_theta"])}
        part = int(cfg["head_dim"] * rope.get("partial_rotary_factor", 1))
        if part != cfg["head_dim"]:
            mine["rotary_dim"] = part
        if rope["rope_type"] == "yarn":
            mine["yarn"] = {k: rope[k] for k in (
                "factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor")}
        elif rope["rope_type"] != "default":
            raise ValueError(f"no rotary rule {rope['rope_type']!r}")
        ropes[kind] = mine
    return tuple(
        {"attention": KINDS[kind], "num_heads": heads, "rope": ropes[kind],
         "mlp": {"dense": "dense", "sparse": "routed"}[mlp]}
        for kind, heads, mlp in zip(cfg["layer_types"],
                                    cfg["num_attention_heads_per_layer"],
                                    cfg["mlp_layer_types"]))


class MixedAttentionMoEJob(BlockDiffusionMoEJob):
    """``LMJob``'s feed, metric and ``fit`` call (next-token labels, the
    cross-entropy's device form) around ``RoutedLM`` built from the
    configuration's own keys (the source's ``config.json`` names) as
    ``BlockDiffusionMoEJob`` builds it: the same share of the experts in a
    buffer of ``buffer_rows`` rows, Adam without a second float32 master,
    each block rematerialised.  The objective is the causal one; each
    layer's kind of attention, head count, rotary rule and feed-forward come
    from ``layer_types``, ``num_attention_heads_per_layer``,
    ``rope_parameters`` and ``mlp_layer_types`` (``layer_records``); the
    gate a head, sigmoid scores, the scale on the routed sum and the shared
    expert are ``RoutedLM``'s switches."""

    metric_names = LMJob.metric_names

    def __init__(self, cfg, traffic, chips, seed):
        Job.__init__(self, cfg, traffic, chips)    # not TransformerLM's
        import jax
        from dt_tpu import config as dt_config, models
        from dt_tpu.parallel import mesh as mesh_lib
        from dt_tpu.training import Module
        dt_config.maybe_force_cpu()
        opt = dict(cfg["optimizer"])
        if not cfg["norm_topk_prob"] or not cfg["gating"] \
                or cfg["moe_apply_router_weight_on_input"]:
            raise ValueError("RoutedExperts renormalises the top-k weights "
                             "and applies them to the experts' outputs, and "
                             "the attention's gate is on")
        model = models.RoutedLM(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            num_experts=cfg["published"]["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate=cfg["moe_intermediate_size"],
            held_experts=(cfg["held_experts_first"], cfg["num_experts"]),
            buffer_rows=cfg["buffer_rows"],
            aux_loss_coef=cfg["aux_loss_coef"], objective="causal",
            layers=layer_records(cfg), window=cfg["sliding_window"],
            dense_intermediate=cfg["intermediate_size"], attn_gate=True,
            qk_norm=False, scoring="sigmoid",
            routed_scale=cfg["moe_routed_scaling_factor"],
            shared_intermediate=cfg["shared_expert_intermediate_size"],
            attention=cfg["attention"], rms_norm_eps=cfg["rms_norm_eps"],
            remat=cfg["remat_blocks"], dtype=_dtype(cfg))
        self.mod = Module(
            model, optimizer=opt.pop("name"),
            optimizer_params={**opt, "multi_precision": False},
            mesh=mesh_lib.make_mesh(devices=jax.local_devices()[:chips]),
            seed=seed % (2 ** 31 - 64))

    sample_shape = LMJob.sample_shape
    fit = LMJob.fit

    def program_tree(self, ref):
        def dense(w):
            return {"kernel": w}
        tree = {"embedding": ref["embed"], "lm_head": ref["head"],
                "final_norm": {"scale": ref["norm_f"]}}
        for i, blk in enumerate(ref["blocks"]):
            out = {
                "input_norm": {"scale": blk["norm"]},
                "attn": {name + "_proj": dense(blk["w" + name[0]])
                         for name in ("q", "k", "v", "o", "gate")},
                "post_norm": {"scale": blk["norm2"]}}
            if "router" in blk:
                out["moe"] = {
                    "router": blk["router"], "gate": blk["gate"],
                    "up": blk["up"], "down": blk["down"],
                    "shared_gate": dense(blk["shared_gate"]),
                    "shared_up": dense(blk["shared_up"]),
                    "shared_down": dense(blk["shared_down"])}
            else:
                out["mlp"] = {"gate": dense(blk["gate"]),
                              "up": dense(blk["up"]),
                              "down": dense(blk["down"])}
            tree[f"block{i}"] = out
        return tree
