"""The job of a decoder of rotary attention and routed experts trained by
diffusion over blocks: ``models.RoutedLM`` through ``Module.fit`` on one
device, the way ``drivers.LMJob`` drives ``TransformerLM``.  Added beside
``drivers.py``; a configuration's file names it under ``driver``.
"""

import numpy as np

from drivers import Job, LMJob, _dtype


class BlockDiffusionMoEJob(LMJob):
    """``LMJob``'s feed and ``fit`` call around ``RoutedLM`` built from the
    configuration's own keys (the source's ``config.json`` names): the
    model is handed ``[xt ; x0]`` (``sample_shape`` is ``(batch, 2 x
    seq_len)``), the loss is the weighted masked cross-entropy and the
    metric its device form.  This chip holds ``num_experts`` experts from
    ``held_experts_first`` of the router's ``published.num_experts``
    outputs, in a buffer of ``buffer_rows`` rows.  Adam without a second
    float32 master (the parameters are float32 already); each block is
    rematerialised."""

    metric_names = ("weighted-ce",)

    def __init__(self, cfg, traffic, chips, seed):
        Job.__init__(self, cfg, traffic, chips)    # not TransformerLM's
        import jax
        from dt_tpu import config as dt_config, models
        from dt_tpu.ops import losses
        from dt_tpu.parallel import mesh as mesh_lib
        from dt_tpu.training import Module
        dt_config.maybe_force_cpu()
        opt = dict(cfg["optimizer"])
        if not cfg["norm_topk_prob"]:
            raise ValueError("RoutedExperts renormalises the top-k weights")
        model = models.RoutedLM(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
            num_experts=cfg["published"]["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate=cfg["moe_intermediate_size"],
            held_experts=(cfg["held_experts_first"], cfg["num_experts"]),
            buffer_rows=cfg["buffer_rows"],
            aux_loss_coef=cfg["aux_loss_coef"],
            block_length=traffic["block_length"],
            attention=cfg["attention"], rms_norm_eps=cfg["rms_norm_eps"],
            remat=cfg["remat_blocks"], dtype=_dtype(cfg))
        self.mod = Module(
            model, loss_fn=losses.weighted_masked_cross_entropy,
            optimizer=opt.pop("name"),
            optimizer_params={**opt, "multi_precision": False},
            mesh=mesh_lib.make_mesh(devices=jax.local_devices()[:chips]),
            seed=seed % (2 ** 31 - 64))

    def sample_shape(self):
        return (self.traffic["batch"], 2 * self.traffic["seq_len"]), np.int32

    def program_tree(self, ref):
        def dense(w):
            return {"kernel": w}
        tree = {"embedding": ref["embed"], "lm_head": ref["head"],
                "final_norm": {"scale": ref["norm_f"]}}
        for i, blk in enumerate(ref["blocks"]):
            tree[f"block{i}"] = {
                "input_norm": {"scale": blk["norm"]},
                "attn": {"q_proj": dense(blk["wq"]), "k_proj": dense(blk["wk"]),
                         "v_proj": dense(blk["wv"]), "o_proj": dense(blk["wo"]),
                         "q_norm": {"scale": blk["q_norm"]},
                         "k_norm": {"scale": blk["k_norm"]}},
                "post_norm": {"scale": blk["norm2"]},
                "moe": {"router": blk["router"], "gate": blk["gate"],
                        "up": blk["up"], "down": blk["down"]}}
        return tree

    def fit(self, feed, callbacks):
        self.mod.fit(feed, eval_metric="weighted-ce", num_epoch=1,
                     batch_end_callback=callbacks)
