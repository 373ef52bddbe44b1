"""The job of a causal decoder of gated short convolutions among grouped-query
attention layers whose routed experts are chosen under a selection bias:
``models.RoutedLM(objective="causal", layers=..., selection_bias=True,
tie_word_embeddings=True)`` through ``Module.fit`` on one device.  Added
beside ``drivers.py``, ``sdar_drivers.py``, ``keye_drivers.py`` and
``laguna_drivers.py``, whose job it extends; a configuration's file names it
under ``driver``.
"""

from drivers import Job, LMJob, _dtype
from laguna_drivers import MixedAttentionMoEJob

#: the source's names of the two kinds of mixer -> the program's
KINDS = {"full_attention": "full", "conv": "conv"}

#: where the program keeps what is state and no parameter
STATE = "batch_stats"


def layer_records(cfg):
    """One record a layer for ``RoutedLM.layers``, read from ``layer_types``
    and ``num_dense_layers`` (the leading layers' feed-forward is dense)."""
    return tuple(
        {"attention": KINDS[kind],
         "mlp": "dense" if i < cfg["num_dense_layers"] else "routed"}
        for i, kind in enumerate(cfg["layer_types"]))


class ShortConvMoEJob(MixedAttentionMoEJob):
    """``LMJob``'s feed, metric and ``fit`` call (next-token labels, the
    cross-entropy's device form) around ``RoutedLM`` built from the
    configuration's own keys (the source's ``config.json`` names) as
    ``MixedAttentionMoEJob`` builds it: the same share of the experts in a
    buffer of ``buffer_rows`` rows, Adam without a second float32 master,
    each block rematerialised.  Each layer's mixer (a gated short
    convolution or causal attention with a norm on each head's query and
    key) and feed-forward come from ``layer_types`` and ``num_dense_layers``
    (``layer_records``); sigmoid scores, the selection bias with its speed,
    the ``1e-6`` of the renormalisation, the scale on the routed sum and the
    tied head are ``RoutedLM``'s switches.

    The selection biases are state and no parameter: the program keeps them
    in ``TrainState.batch_stats``, and ``make_state`` puts the reference's
    seeded start there (``state_tree``).  They are no leaves of the trees
    the comparison reads: a bias moves by 0.001 a step where Adam moves a
    parameter by 1e-7, so beside the parameters they are nine tenths of the
    change's squared norm and one limit cannot hold both (PERF.md section 6,
    PR 43).  The CPU tests hold the program's biases to the reference's
    after every step, and ``moe.bias_moved_assignments_pct`` reads on the
    chip that the selection uses them."""

    def __init__(self, cfg, traffic, chips, seed):
        Job.__init__(self, cfg, traffic, chips)    # not TransformerLM's
        import jax
        from dt_tpu import config as dt_config, models
        from dt_tpu.parallel import mesh as mesh_lib
        from dt_tpu.training import Module
        dt_config.maybe_force_cpu()
        opt = dict(cfg["optimizer"])
        if not cfg["norm_topk_prob"] or not cfg["use_expert_bias"] \
                or cfg["conv_bias"] or not cfg["tie_word_embeddings"]:
            raise ValueError("RoutedExperts renormalises the top-k weights, "
                             "the selection bias is on, the conv mixer has "
                             "no bias and the head is the table")
        heads = cfg["num_attention_heads"]
        model = models.RoutedLM(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"], num_heads=heads,
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // heads,
            rope_theta=float(cfg["rope_theta"]),
            num_experts=cfg["published"]["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            moe_intermediate=cfg["moe_intermediate_size"],
            held_experts=(cfg["held_experts_first"], cfg["num_experts"]),
            buffer_rows=cfg["buffer_rows"],
            aux_loss_coef=cfg["aux_loss_coef"], objective="causal",
            layers=layer_records(cfg), conv_taps=cfg["conv_L_cache"],
            dense_intermediate=cfg["intermediate_size"], scoring="sigmoid",
            routed_scale=float(cfg["routed_scaling_factor"]),
            router_norm_eps=cfg["norm_topk_eps"], selection_bias=True,
            bias_update_speed=cfg["expert_bias_update_speed"],
            tie_word_embeddings=True, attention=cfg["attention"],
            rms_norm_eps=cfg["norm_eps"], remat=cfg["remat_blocks"],
            dtype=_dtype(cfg))
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
        tree = {"embedding": ref["embed"],
                "final_norm": {"scale": ref["norm_f"]}}
        for i, blk in enumerate(ref["blocks"]):
            out = {"input_norm": {"scale": blk["norm"]},
                   "post_norm": {"scale": blk["norm2"]}}
            if "win" in blk:
                out["conv"] = {"in_proj": dense(blk["win"]),
                               "conv_kernel": blk["taps"],
                               "out_proj": dense(blk["wout"])}
            else:
                out["attn"] = {
                    **{name + "_proj": dense(blk["w" + name])
                       for name in ("q", "k", "v", "o")},
                    "q_norm": {"scale": blk["q_norm"]},
                    "k_norm": {"scale": blk["k_norm"]}}
            if "router" in blk:
                out["moe"] = {name: blk[name]
                              for name in ("router", "gate", "up", "down")}
            else:
                out["mlp"] = {name: dense(blk[name])
                              for name in ("gate", "up", "down")}
            tree[f"block{i}"] = out
        return tree

    def state_tree(self, ref):
        """The reference's selection biases under the program's names in
        ``batch_stats``."""
        return {f"block{i}": {"moe": {"selection_bias": blk["bias"]}}
                for i, blk in enumerate(ref["blocks"]) if "bias" in blk}

    def make_state(self, ref_init, key):
        """``Job.make_state`` with the biases as the reference's ``init``
        drew them, where ``Job`` gives batch-norm's zeros and ones."""
        import jax
        import jax.numpy as jnp
        from dt_tpu.parallel import mesh as mesh_lib
        from dt_tpu.training.train_state import TrainState
        mod = self.mod
        shape, dtype = self.sample_shape()
        want = jax.eval_shape(lambda: mod.model.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros(shape, dtype),
            training=False))
        want = {name: want[name] for name in ("params", STATE)}
        shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: (a.shape, a.dtype), t)

        def build(k):
            ref = ref_init(k, self.cfg)
            made = {"params": self.program_tree(ref),
                    STATE: self.state_tree(ref)}
            if shapes(made) != shapes(want):
                raise ValueError("the reference's weights do not fit the "
                                 f"program's tree:\n{shapes(made)}\n!=\n"
                                 f"{shapes(want)}")
            return TrainState.create(mod.model.apply, made["params"], mod.tx,
                                     made[STATE])

        self._ref_init = ref_init
        mod.state = jax.jit(build, out_shardings=mesh_lib.replicate_sharding(
            mod.mesh))(key)
        return mod.state
