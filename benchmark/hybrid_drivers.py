"""The job of a decoder whose layers are read from a pattern (state-space and
attention layers): ``models.HybridLM`` through ``Module.fit`` on one device,
the way ``drivers.LMJob`` drives ``TransformerLM``.  Added beside
``drivers.py``; a configuration's file names it under ``driver``.
"""

from drivers import Job, LMJob, _dtype


class HybridLMJob(LMJob):
    """``LMJob``'s feed, metric and ``fit`` call around another model:
    ``HybridLM`` built from the configuration's own keys (the source's
    ``config.json`` names), Adam without a second float32 master: the
    parameters are float32 already, and a master would be an exact copy,
    4 bytes a parameter that the chip does not have (the configuration's
    ``assumed`` says so).  Each block is rematerialised."""

    def __init__(self, cfg, traffic, chips, seed):
        Job.__init__(self, cfg, traffic, chips)    # not TransformerLM's
        import jax
        from dt_tpu import config as dt_config, models
        from dt_tpu.parallel import mesh as mesh_lib
        from dt_tpu.training import Module
        dt_config.maybe_force_cpu()
        opt = dict(cfg["optimizer"])
        model = models.HybridLM(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            layer_types=tuple(cfg["layer_types"]),
            intermediate=cfg["shared_intermediate_size"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            attention_multiplier=cfg["attention_multiplier"],
            attention=cfg["attention"],
            ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
            ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
            ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"],
            ssm_conv_bias=cfg["mamba_conv_bias"],
            embedding_multiplier=cfg["embedding_multiplier"],
            residual_multiplier=cfg["residual_multiplier"],
            logits_scaling=cfg["logits_scaling"],
            tie_word_embeddings=cfg["tie_word_embeddings"],
            rms_norm_eps=cfg["rms_norm_eps"], remat=cfg["remat_blocks"],
            dtype=_dtype(cfg))
        self.mod = Module(
            model, optimizer=opt.pop("name"),
            optimizer_params={**opt, "multi_precision": False},
            mesh=mesh_lib.make_mesh(devices=jax.local_devices()[:chips]),
            seed=seed % (2 ** 31 - 64))

    def program_tree(self, ref):
        def dense(w):
            return {"kernel": w}
        tree = {"embedding": ref["embed"],
                "final_norm": {"scale": ref["norm_f"]}}
        for i, (blk, kind) in enumerate(zip(ref["blocks"],
                                            self.cfg["layer_types"])):
            if kind == "mamba":
                mixer = {"in_proj": dense(blk["in_proj"]),
                         "conv_kernel": blk["conv_w"],
                         "conv_bias": blk["conv_b"],
                         "dt_bias": blk["dt_bias"], "A_log": blk["A_log"],
                         "D": blk["D"], "norm_scale": blk["norm_g"],
                         "out_proj": dense(blk["out_proj"])}
            else:
                mixer = {"q_proj": dense(blk["wq"]), "k_proj": dense(blk["wk"]),
                         "v_proj": dense(blk["wv"]), "o_proj": dense(blk["wo"])}
            tree[f"block{i}"] = {
                "input_norm": {"scale": blk["norm"]},
                "mamba" if kind == "mamba" else "attn": mixer,
                "post_norm": {"scale": blk["norm2"]},
                "mlp": {"gate": dense(blk["gate"]), "up": dense(blk["up"]),
                        "down": dense(blk["down"])}}
        return tree
