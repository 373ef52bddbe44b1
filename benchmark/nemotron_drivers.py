"""The job of a decoder whose blocks are each one part alone (a Mamba-2
mixer, grouped-query attention, or routed experts in a latent), every part a
held share of its heads or experts: ``models.PatternLM`` through
``Module.fit`` on one device.  Added beside ``drivers.py`` and the other
jobs' modules, the last of which (``lfm2_drivers.py``) it extends; a
configuration's file names it under ``driver``.
"""

from drivers import Job, LMJob, _dtype
from lfm2_drivers import ShortConvMoEJob


def whole(cfg, key):
    """The whole model's value of ``key``: ``published``'s where the file
    holds a share, the file's own otherwise."""
    return cfg.get("published", {}).get(key, cfg[key])


def share(cfg, first, key):
    """``(first, count)`` of the whole model's ``key``, or None where the
    file holds them all."""
    return None if whole(cfg, key) == cfg[key] else (cfg[first], cfg[key])


class SinglePartHybridJob(ShortConvMoEJob):
    """``LMJob``'s feed, metric and ``fit`` call (next-token labels, the
    cross-entropy's device form) around ``PatternLM`` built from the
    configuration's own keys (the source's ``config.json`` names), as
    ``ShortConvMoEJob`` builds ``RoutedLM``: Adam without a second float32
    master, each block rematerialised, the selection biases seeded from the
    reference's ``init`` into ``TrainState.batch_stats`` (``make_state``,
    ``state_tree``) and no leaves of the trees the comparison reads.

    The file's counts are what this chip holds and ``published`` has the
    whole model's: the model is given the whole head counts, groups and
    router width, and each share as ``(first, count)`` (``share``)."""

    def __init__(self, cfg, traffic, chips, seed):
        Job.__init__(self, cfg, traffic, chips)    # not TransformerLM's
        import jax
        from dt_tpu import config as dt_config, models
        from dt_tpu.parallel import mesh as mesh_lib
        from dt_tpu.training import Module
        dt_config.maybe_force_cpu()
        opt = dict(cfg["optimizer"])
        if not cfg["norm_topk_prob"] or cfg["mlp_hidden_act"] != "relu2" \
                or cfg["n_group"] != 1 or cfg["n_shared_experts"] != 1 \
                or cfg["tie_word_embeddings"] or not cfg["use_conv_bias"] \
                or cfg["use_bias"] or cfg["mamba_proj_bias"]:
            raise ValueError("PatternLM renormalises the top-k weights over "
                             "one group of experts, its experts and its one "
                             "shared expert are squared-ReLU, the head is "
                             "its own and only the convolution has a bias")
        model = models.PatternLM(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            pattern=cfg["hybrid_override_pattern"],
            num_heads=whole(cfg, "num_attention_heads"),
            num_kv_heads=whole(cfg, "num_key_value_heads"),
            head_dim=cfg["head_dim"],
            held_heads=share(cfg, "held_attention_heads_first",
                             "num_attention_heads"),
            attention=cfg["attention"],
            ssm_heads=whole(cfg, "mamba_num_heads"),
            ssm_head_dim=cfg["mamba_head_dim"],
            ssm_state=cfg["ssm_state_size"],
            ssm_groups=whole(cfg, "n_groups"),
            ssm_conv=cfg["conv_kernel"], ssm_chunk=cfg["chunk_size"],
            held_ssm_heads=share(cfg, "held_mamba_heads_first",
                                 "mamba_num_heads"),
            num_experts=whole(cfg, "n_routed_experts"),
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate=cfg["moe_intermediate_size"],
            moe_latent=cfg["moe_latent_size"],
            shared_intermediate=cfg["moe_shared_expert_intermediate_size"],
            held_experts=share(cfg, "held_experts_first", "n_routed_experts"),
            buffer_rows=cfg["buffer_rows"],
            routed_scale=float(cfg["routed_scaling_factor"]),
            router_norm_eps=cfg["norm_topk_eps"],
            aux_loss_coef=cfg["aux_loss_coef"],
            bias_update_speed=cfg["expert_bias_update_speed"],
            rms_norm_eps=cfg["layer_norm_epsilon"],
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
        for i, (blk, kind) in enumerate(zip(
                ref["blocks"], self.cfg["hybrid_override_pattern"])):
            if kind == "M":
                part = {"mamba": {
                    "in_proj": dense(blk["in_proj"]),
                    "conv_kernel": blk["conv_w"], "conv_bias": blk["conv_b"],
                    "dt_bias": blk["dt_bias"], "A_log": blk["A_log"],
                    "D": blk["D"], "norm_scale": blk["norm_g"],
                    "out_proj": dense(blk["out_proj"])}}
            elif kind == "*":
                part = {"attn": {name + "_proj": dense(blk["w" + name])
                                 for name in ("q", "k", "v", "o")}}
            else:
                part = {"moe": {
                    "router": blk["router"], "up": blk["up"],
                    "down": blk["down"],
                    "latent_in": dense(blk["lat_in"]),
                    "latent_out": dense(blk["lat_out"]),
                    "shared_up": dense(blk["shared_up"]),
                    "shared_down": dense(blk["shared_down"])}}
            tree[f"block{i}"] = {"norm": {"scale": blk["norm"]}, **part}
        return tree
