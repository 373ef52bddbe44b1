"""The job of a causal decoder of routed experts whose attention reads only
the keys a learned index picks: ``models.RoutedLM(objective="causal",
indexer=...)`` through ``Module.fit`` on one device.  Added beside
``drivers.py`` and ``sdar_drivers.py``, whose job it extends; a
configuration's file names it under ``driver``.
"""

from drivers import Job, LMJob, _dtype
from sdar_drivers import BlockDiffusionMoEJob


class SparseIndexMoEJob(BlockDiffusionMoEJob):
    """``LMJob``'s feed, metric and ``fit`` call (next-token labels, the
    cross-entropy's device form) around ``RoutedLM`` built from the
    configuration's own keys (the source's ``config.json`` names) as
    ``BlockDiffusionMoEJob`` builds it: the same share of the experts in a
    buffer of ``buffer_rows`` rows, Adam without a second float32 master,
    each block rematerialised.  The objective is the causal one, the index
    is ``sa_config``'s, and the three-part rotary positions are
    ``rope_scaling.mrope_section``'s (the model hands itself three equal
    rows: text)."""

    metric_names = LMJob.metric_names

    def __init__(self, cfg, traffic, chips, seed):
        Job.__init__(self, cfg, traffic, chips)    # not TransformerLM's
        import jax
        from dt_tpu import config as dt_config, models
        from dt_tpu.parallel import mesh as mesh_lib
        from dt_tpu.training import Module
        dt_config.maybe_force_cpu()
        opt = dict(cfg["optimizer"])
        sa = cfg["sa_config"]
        if not cfg["norm_topk_prob"] or sa["indexer_num_kv_heads"] != 1:
            raise ValueError("RoutedExperts renormalises the top-k weights, "
                             "and the index has one key a position")
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
            aux_loss_coef=cfg["aux_loss_coef"], objective="causal",
            indexer=dict(heads=sa["indexer_num_heads"],
                         head_dim=sa["indexer_head_dim"], top_k=sa["topk"],
                         q_chunk=sa["q_chunk_size"],
                         kv_chunk=sa["kv_chunk_size"],
                         kl_weight=cfg["indexer_kl_weight"]),
            mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
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
        tree = super().program_tree(ref)
        for i, blk in enumerate(ref["blocks"]):
            tree[f"block{i}"]["attn"].update(
                index_q={"kernel": blk["wq_i"]},
                index_k={"kernel": blk["wk_i"]},
                index_w={"kernel": blk["ww_i"]},
                index_k_norm={"scale": blk["k_norm_i"],
                              "bias": blk["k_bias_i"]})
        return tree
