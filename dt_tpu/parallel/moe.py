"""Mixture-of-experts layers: expert parallelism over the mesh.

The reference caps out at data parallelism + manual model parallelism
(``python/mxnet/module/executor_group.py:143`` group2ctx placement;
SURVEY §2.3 parallelism inventory); this framework treats distributed
execution as first-class, so the sharding family is completed with
expert parallelism.  Two layers.

``RoutedExperts`` is the layer the models use (the sparse-expert decoders
of ``models/routed_lm.py``): ``k > 1`` routing over the share of the
experts one chip holds, the held assignments sorted into a static buffer,
the experts' grouped products over it (``ops/pallas/grouped.py``: three
where an expert is a gated-SiLU feed-forward, two where it is a squared-ReLU
one without a gate), at the stream's width or, with ``latent``, at a
narrower one that two projections lead into and out of.  Scores are the
softmax over all the router's outputs or, with ``scoring="sigmoid"``, each
output's own sigmoid; the top ``k`` are renormalised to sum to one (plus
``norm_eps``) and may carry a scale (``routed_scale``); a shared expert
that every token visits (``shared_intermediate``) is added unweighted and
is every chip's alike.  Balance is either the Switch auxiliary term
(``aux_weight``) or, with ``selection_bias``, a bias on the scores **for
the selection only** (arXiv:2408.15664; arXiv:2412.19437 section 2.1.2):
``S = top_k(s + bias)`` while the weights are gathered from ``s``; the bias
is no parameter (a variable of the ``batch_stats`` collection that
``training.Module`` carries through ``fit``, checkpoints and a joiner's
bootstrap; read under ``stop_gradient``, so no gradient reaches it and the
optimizer holds nothing for it), and a training step moves it against the
load its own selection made: ``bias_e += u sign(mean_e(load) - load_e)``.

``MoEMLP`` is the small top-1 layer of the sharding examples and tests:
Switch-Transformer-style routing (Fedus et al. 2021, public recipe), fixed
expert capacity ``C = ceil(T/E * capacity_factor)``, overflow tokens
dropped (their output is 0 and the residual path carries them), auxiliary
load-balancing loss ``E * sum_e f_e * P_e``; fixed-shape one-hot einsum
dispatch, no sorting.  A plain module on one device; with ``mesh`` +
``axis`` the expert dimension of the weights and the dispatched
activations is sharding-constrained to that axis, and the dispatch/combine
einsums carry GSPMD-inserted all_to_all-style collectives over ICI.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as linen
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dt_tpu.ops.pallas.grouped import grouped_matmul

Array = jax.Array


def switch_route(logits: Array, capacity: int):
    """Top-1 capacity routing.

    ``logits``: (T, E).  Returns (dispatch (T, E, C) bool-ish float,
    combine (T, E, C) float, aux_loss scalar).  Token t goes to its
    argmax expert e at slot ``position_in_expert`` if that is < C;
    ``combine`` carries the gate probability, ``dispatch`` is the 0/1
    routing mask (identical support)."""
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, axis=-1)                     # (T,)
    expert = jnp.argmax(probs, axis=-1)                # (T,)
    onehot = jax.nn.one_hot(expert, e, dtype=logits.dtype)  # (T, E)
    # position of each token within its expert's queue (arrival order)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0    # (T, E), -1 if not
    pos_of_token = jnp.sum(pos * onehot, axis=-1)      # (T,)
    keep = pos_of_token < capacity
    slot = jax.nn.one_hot(pos_of_token.astype(jnp.int32), capacity,
                          dtype=logits.dtype)
    dispatch = onehot[:, :, None] * slot[:, None, :] \
        * keep[:, None, None]                          # (T, E, C)
    combine = dispatch * gate[:, None, None]
    # load-balancing auxiliary (Switch eq. 4): E * sum_e f_e * P_e
    f = jnp.mean(onehot, axis=0)                       # fraction routed
    p = jnp.mean(probs, axis=0)                        # mean router prob
    aux = e * jnp.sum(f * p)
    return dispatch, combine, aux


class MoEMLP(linen.Module):
    """Expert-parallel MLP block (drop-in for a dense FFN).

    ``x`` (B, S, D) -> (B, S, D); sows the load-balancing loss under
    ``("aux_loss", "moe")``.  With ``mesh``/``axis`` set, expert weights
    and dispatched activations are constrained to shard over that axis.
    """
    num_experts: int = 4
    hidden_ratio: int = 4
    capacity_factor: float = 1.25
    aux_weight: float = 0.01   # Switch paper's alpha; sown PRE-weighted
    mesh: Any = None
    axis: str = "model"
    dtype: Any = jnp.float32

    @linen.compact
    def __call__(self, x: Array) -> Array:
        b, s, d = x.shape
        e = self.num_experts
        h = d * self.hidden_ratio
        tokens = x.reshape(b * s, d)
        t = tokens.shape[0]
        capacity = max(1, int(-(-t // e) * self.capacity_factor))

        logits = linen.Dense(e, use_bias=False, dtype=jnp.float32,
                             name="router")(tokens.astype(jnp.float32))
        dispatch, combine, aux = switch_route(logits, capacity)
        # pre-weighted so generic training loops (Module.fit) can add the
        # whole ``aux_loss`` collection to the objective unscaled
        self.sow("aux_loss", "moe", self.aux_weight * aux)

        wi = self.param("wi", linen.initializers.lecun_normal(),
                        (e, d, h), jnp.float32).astype(self.dtype)
        wo = self.param("wo", linen.initializers.lecun_normal(),
                        (e, h, d), jnp.float32).astype(self.dtype)

        def ep(arr, spec):
            if self.mesh is None:
                return arr
            from jax.sharding import NamedSharding, PartitionSpec as P
            return jax.lax.with_sharding_constraint(
                arr, NamedSharding(self.mesh, P(*spec)))

        wi = ep(wi, (self.axis, None, None))
        wo = ep(wo, (self.axis, None, None))
        # dispatch: (T, E, C) x (T, D) -> (E, C, D); under EP the E axis
        # is sharded, so GSPMD turns this into the all_to_all scatter
        xin = jnp.einsum("tec,td->ecd", dispatch.astype(self.dtype),
                         tokens.astype(self.dtype))
        xin = ep(xin, (self.axis, None, None))
        hmid = jax.nn.relu(jnp.einsum("ecd,edh->ech", xin, wi))
        hmid = ep(hmid, (self.axis, None, None))
        xout = jnp.einsum("ech,ehd->ecd", hmid, wo)
        xout = ep(xout, (self.axis, None, None))
        out = jnp.einsum("tec,ecd->td", combine.astype(self.dtype), xout)
        return out.reshape(b, s, d)


# ---------------------------------------------------------------------------
# k > 1 routing over a share of the experts: what expert parallelism asks of
# one chip.  The layer is told which experts it holds, routes over all of
# them, and computes the part of the result that its own give.
# ---------------------------------------------------------------------------

#: the columns of the ``counters`` a ``RoutedExperts`` layer sows for each
#: row of the batch: that row's assignments to each expert held, in order,
#: then their sum, those among them that found no room in the buffer, and
#: all the row made (``S x k``)
COUNTER_TAIL = ("held", "overflow", "assignments")

#: the columns of the second ``counters`` row a layer with a selection bias
#: sows (``("counters", "moe_bias")``) for each row of the batch: the
#: ``(token, slot)`` picks that ``top_k(scores)`` alone would not have made,
#: and all the row made
BIAS_COUNTERS = ("moved", "assignments")


def route_top_k(logits: Array, k: int, scoring: str = "softmax",
                bias: Optional[Array] = None, norm_eps: float = 0.0):
    """``logits`` (T, E) float32 -> (experts (T, k) int32, weights (T, k),
    probs (T, E)): the scores over all ``E``, their ``k`` largest, and
    those weights renormalised to sum to one a token (``norm_eps`` added to
    the sum where it is not zero).  ``scoring``
    ``"softmax"``: the scores are the softmax, and ``probs`` are they;
    ``"sigmoid"``: each expert's score is the sigmoid of its own logit (they
    do not compete before the top-k), and ``probs``, what the
    load-balancing term reads, are the scores normalised to sum to one a
    token.  ``bias`` (E,): the ``k`` experts are those of largest ``scores +
    bias``, and the weights are their *unbiased* scores: the bias decides
    who is chosen and never how much a chosen expert counts."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        scores = probs = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        raise ValueError(f"no scoring {scoring!r}")
    if bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights / (total + norm_eps if norm_eps else total)
    return experts.astype(jnp.int32), weights, probs


def selection_load(experts: Array, num_experts: int) -> Array:
    """The selection ``experts`` (T, k) counted, once a layer call: ``load``
    (num_experts,) int32, the assignments to each router output; the
    load-balancing term, the selection bias and the buffer's group sizes
    all read this one array.  A dense sum and no scatter-add (which takes
    the chip about 9 ns for each of the ``T x k`` scalar updates): an
    output's number is ``32 high + low``, and the count of the picks with
    that pair is the product, on the matrix unit, of two one-hot matrices of
    0 and 1, ``(E / 32, T x k)`` by ``(32, T x k)``: ``T x k x (E / 32 +
    32)`` compares where a one-hot over all outputs makes ``T x k x E``, and
    no array of that size in any form of the program.  Exact: sums of ones
    in float32, for fewer than 2^24 picks."""
    if experts.size >= 1 << 24:
        raise ValueError(f"{experts.size} picks: float32 counts to 2^24")
    flat = experts.reshape(1, -1)

    def one_hot(n, part):
        return (jnp.arange(n, dtype=flat.dtype)[:, None] == part).astype(
            jnp.bfloat16)
    pairs = jax.lax.dot_general(
        one_hot(-(-num_experts // 32), flat >> 5), one_hot(32, flat & 31),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return pairs.reshape(-1)[:num_experts].astype(jnp.int32)


def moved_bias(bias: Array, load: Array, speed: float) -> Array:
    """The selection bias after a step whose selection made ``load``
    (``selection_load`` over all ``E = len(bias)`` router outputs): ``bias_e
    + speed * sign(mean_e(load) - load_e)`` (arXiv:2408.15664's rule): an
    expert under the mean load is easier to choose next step, one over it
    harder, one at it unmoved."""
    load = load.astype(jnp.float32)     # counts below 2**24: exact
    return bias + speed * jnp.sign(jnp.mean(load) - load)


def load_balancing_term(load: Array, probs: Array) -> Array:
    """Switch eq. 4 for ``k`` experts a token: ``E * sum_e f_e P_e`` with
    ``f_e = load_e / T`` the assignments to expert ``e`` over the tokens
    (``selection_load``; they sum to ``k``; a count, so no gradient) and
    ``P_e`` the mean router probability; ``k`` where the load is even."""
    t, e = probs.shape
    f = load.astype(jnp.float32) / t
    return e * jnp.sum(jax.lax.stop_gradient(f) * jnp.mean(probs, axis=0))


def sort_held(experts: Array, load: Array, first: int, count: int,
              rows: int):
    """The assignments ``experts`` (T, k) to the ``count`` experts from
    ``first``, sorted by expert into a buffer of ``rows`` rows ->
    (assignment (rows,), sizes (count,), held (count,)): the flat index
    ``token * k + slot`` each row holds, how many rows each expert has in
    the buffer, and how many assignments it had, which is its part of
    ``load`` (``selection_load(experts, E)``; ``held - sizes`` found no
    room: the buffer fills in expert order).  Rows past the load hold
    assignments to experts that are not here, in order; their weight is
    zero (``RoutedExperts``)."""
    local = experts.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(local, stable=True)[:rows].astype(jnp.int32)
    held = load[first:first + count]
    ends = jnp.minimum(jnp.cumsum(held), rows)
    sizes = jnp.diff(ends, prepend=0)
    return order, sizes, held


class RoutedExperts(linen.Module):
    """A layer of ``num_experts`` gated-SiLU experts, ``top_k`` a token, of
    which this chip holds ``held = (first, count)`` (all of them where
    None)::

        p = softmax_f32(x Wr) over num_experts ;  S = top_k(p)
        w_e = p_e / sum_S p
        y = sum_{e in S, e held} w_e * Wdown_e(silu(x Wgate_e) * (x Wup_e))

    Two facts of a model beside those, set from its configuration (the
    defaults are the layer above).  ``expert_form`` ``"relu2"``: an expert
    is ``Wdown_e(relu(x Wup_e)^2)``, squared ReLU and no gate: two grouped
    products where the gated form has three, and no parameter ``gate`` (nor
    the name ``moe_gate``); the shared expert takes the same form
    (``shared_up``, ``shared_down``).  ``latent``: the experts work at that
    width and not the stream's::

        l = x Wlin                                      (d -> latent, module latent_in)
        r = sum_{e in S, e held} w_e * expert_e(l)      Wup_e latent x I, Wdown_e I x latent
        y = r Wlout                                     (latent -> d, module latent_out)

    The router still reads ``x`` at the stream's width; the dispatch, the
    grouped products and the combine run at ``latent`` (what an
    expert-parallel exchange would carry); ``Wlout`` takes the routed sum
    back, so summing the shares' ``r`` and projecting once is summing their
    ``y``: both projections are every chip's alike, as the router is.  The
    shared expert reads ``x`` itself and is added after.  The two
    projections lie under ``jax.named_scope("latent")``.

    Five switches, whose defaults leave that as it is.  ``scoring``
    ``"sigmoid"``: ``p = sigmoid_f32(x Wr)``, each expert scored alone
    (``route_top_k``; the load-balancing term then reads ``p / sum_E p``).
    ``routed_scale``: ``w_e = routed_scale * p_e / sum_S p``, a scale on the
    routed sum.  ``shared_intermediate``: a shared expert of that width
    that every token visits, ``y += Wdown_s(silu(x Wgate_s) * (x Wup_s))``,
    a plain gated-SiLU feed-forward (modules ``shared_gate``, ``shared_up``,
    ``shared_down`` under ``jax.named_scope("shared")``), unweighted and
    computed by every chip alike: summing the shares of a layer over the
    chips counts it once.  ``norm_eps``: ``w_e = p_e / (sum_S p +
    norm_eps)``.  ``selection_bias``: ``S = top_k(p + bias)`` with the
    weights still gathered from ``p``; ``bias`` (``num_experts``,) float32
    is the variable ``selection_bias`` of the collection ``batch_stats``
    (zeros from ``init``; ``training.Module`` carries the collection in its
    ``TrainState`` beside the parameters), read under ``stop_gradient``.
    Where that collection is mutable and the layer is not being
    initialised, which is a training step under ``Module`` (evaluation
    applies the model without it), the layer writes ``moved_bias``: the
    bias it selected with, moved by ``bias_update_speed`` against the load
    its own selection made over all ``num_experts`` outputs and all the
    tokens (not the held experts' alone: every chip that shares the layer
    moves the same bias alike).  The step's selection used the bias from
    before the step.  The write is the forward pass's: a rematerialised
    block's second forward computes it again and nobody reads that copy
    (once a step; under ``Module(grad_accum=n)`` once a micro-batch, each
    selecting with the bias the one before it left, as batch-norm's
    statistics chain).  Such a layer also sows ``("counters",
    "moe_bias")``, for each row of the batch the picks the bias moved (the
    chosen experts that ``top_k(p)`` would not have chosen) and all it
    made (``BIAS_COUNTERS``).

    What the experts that are not held would have added is left out: on one
    chip the layer runs without its exchange, and no code stands in for the
    other chips.  The router is float32 at its full width (its product at
    ``highest`` precision).  The selection is counted once a call
    (``selection_load``, over all ``num_experts`` outputs), and the
    load-balancing term, the bias's move and the buffer's group sizes read
    that one array.  The assignments to held experts are sorted by
    expert into a buffer of ``buffer_rows`` rows, the receive side of an
    expert-parallel exchange, and the experts' grouped products (three, or
    two under ``expert_form="relu2"``;
    ``ops.pallas.grouped.grouped_matmul``: a Pallas kernel each where the
    widths are whole lane tiles, ``jax.lax.ragged_dot`` at toy sizes) run
    over the whole buffer: rows past the load are padding the products still
    multiply (they go through the last expert with weight zero) and the
    kernels' grid does not read the sizes, so a step costs the same
    whatever the router decides.  ``buffer_rows=None`` is
    the exact worst case ``T x top_k``.  An assignment that finds no room
    is dropped from the result and counted, never silently lost.

    Sows ``aux_weight`` times the Switch load-balancing term over all
    ``num_experts`` router outputs under ``("aux_loss", "load_balance")``
    (``Module`` adds the collection to the objective), and under
    ``("counters", "moe")`` for each row of the batch its assignments to
    each held expert, their sum, those dropped, and all it made
    (``COUNTER_TAIL``):
    ``Module`` hands them to the host with the metric's statistics.
    ``jax.named_scope``s ``route``, ``dispatch``, ``experts`` and
    ``combine`` (and ``latent``, ``shared``) tell the parts apart in an
    operation's scope path.

    **A held share computes its experts' part of the routed sum, and summing
    the shares gives the layer**: each assignment lies with exactly one
    share, its weight comes from the router every share computes alike, and
    what is every chip's alike (the shared expert; with ``latent`` the
    projection out, which is linear) is counted once."""
    num_experts: int
    top_k: int
    intermediate: int
    held: Optional[tuple] = None          # (first, count)
    buffer_rows: Optional[int] = None     # None: T x top_k
    aux_weight: float = 0.0
    dtype: Any = jnp.float32
    scoring: str = "softmax"              # or 'sigmoid'
    routed_scale: float = 1.0
    shared_intermediate: Optional[int] = None
    norm_eps: float = 0.0
    selection_bias: bool = False
    bias_update_speed: float = 0.001
    expert_form: str = "gated_silu"       # or 'relu2': no gate
    latent: Optional[int] = None          # the experts' width; None: d

    @linen.compact
    def __call__(self, x: Array) -> Array:
        b, s, d = x.shape
        k = self.top_k
        first, count = self.held or (0, self.num_experts)
        tokens = x.reshape(b * s, d)
        t = b * s
        rows = t * k if self.buffer_rows is None else int(self.buffer_rows)
        if self.expert_form not in ("gated_silu", "relu2"):
            raise ValueError(f"no expert form {self.expert_form!r}")
        gated = self.expert_form == "gated_silu"
        width = self.latent or d          # what the experts read and write
        init = linen.initializers.normal(0.02)
        router = self.param("router", init, (d, self.num_experts),
                            jnp.float32)
        into = (count, width, self.intermediate)
        w_gate = self.param("gate", init, into, jnp.float32) if gated else None
        w_up = self.param("up", init, into, jnp.float32)
        w_down = self.param("down", init, (count, self.intermediate, width),
                            jnp.float32)
        dense = lambda n, name: linen.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)

        with jax.named_scope("route"):
            logits = jnp.dot(tokens.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            if self.selection_bias:
                experts, weights, probs, load = self._route_biased(logits, b)
            else:
                experts, weights, probs = route_top_k(
                    logits, k, self.scoring, norm_eps=self.norm_eps)
                load = selection_load(experts, self.num_experts)
            if self.routed_scale != 1.0:
                weights = weights * self.routed_scale
            order, sizes, _ = sort_held(experts, load, first, count, rows)
            # what route hands on, under one name a block's remat policy
            # can keep (models/routed_lm.py SAVED): with these held the
            # backward pass neither sorts nor counts again.  probs carries
            # no name: nothing in the backward pass reads it (the softmax's
            # own derivative keeps its own copy)
            experts, weights, order, source, sizes, load = checkpoint_name(
                (experts, weights, order, order // k, sizes, load),
                "moe_route")
            if self.aux_weight:
                self.sow("aux_loss", "load_balance", self.aux_weight
                         * load_balancing_term(load, probs))
            placed = jnp.arange(rows) < jnp.sum(sizes)
            # the padding goes through the last expert: every row of the
            # buffer is in a group, and the products' cost is the buffer's
            groups = sizes.at[count - 1].add(rows - jnp.sum(sizes))
            row_weight = jnp.where(placed, weights.reshape(-1)[order], 0.0)
            self._count(experts, first, count, order, placed, b, s * k)
        if self.latent:
            with jax.named_scope("latent"):
                tokens = checkpoint_name(
                    dense(width, "latent_in")(tokens), "moe_latent")
        with jax.named_scope("dispatch"):
            buf = jnp.take(tokens, source, axis=0).astype(self.dtype)
        with jax.named_scope("experts"):
            grouped = lambda lhs, w: grouped_matmul(  # noqa: E731
                lhs, w.astype(self.dtype), groups, jnp.float32)
            # the products in the float32 they are made in, by name
            if gated:
                hidden = jax.nn.silu(
                    checkpoint_name(grouped(buf, w_gate), "moe_gate")) \
                    * checkpoint_name(grouped(buf, w_up), "moe_up")
            else:
                hidden = jnp.square(jax.nn.relu(
                    checkpoint_name(grouped(buf, w_up), "moe_up")))
            out = grouped(hidden.astype(self.dtype), w_down)
        with jax.named_scope("combine"):
            out = (out * row_weight[:, None]).astype(self.dtype)
            y = jnp.zeros((t, width), self.dtype).at[source].add(out)
        if self.latent:
            with jax.named_scope("latent"):
                y = dense(d, "latent_out")(y)
        y = y.reshape(b, s, d)
        if self.shared_intermediate:
            with jax.named_scope("shared"):
                wide = self.shared_intermediate
                if gated:
                    hidden = jax.nn.silu(
                        checkpoint_name(dense(wide, "shared_gate")(x),
                                        "shared_gate")) \
                        * checkpoint_name(dense(wide, "shared_up")(x),
                                          "shared_up")
                else:
                    hidden = jnp.square(jax.nn.relu(checkpoint_name(
                        dense(wide, "shared_up")(x), "shared_up")))
                y = y + dense(d, "shared_down")(hidden)
        return y

    def _route_biased(self, logits, b):
        """``route_top_k`` under the layer's selection bias, with the
        selection's ``selection_load``; the bias moved against that load
        where the step may write it, and the picks it moved counted."""
        k = self.top_k
        held = self.variable("batch_stats", "selection_bias", jnp.zeros,
                             (self.num_experts,), jnp.float32)
        bias = jax.lax.stop_gradient(held.value)
        experts, weights, probs = route_top_k(
            logits, k, self.scoring, bias, self.norm_eps)
        load = selection_load(experts, self.num_experts)
        if not self.is_initializing() \
                and self.is_mutable_collection("batch_stats"):
            held.value = moved_bias(bias, load, self.bias_update_speed)
        # a second top-k and a T x k x k compare, for the counter alone: 1.1
        # ms a layer less on the chip than a gather of the chosen experts'
        # scores held against the k-th largest (PERF.md section 6, PR 43)
        plain, _, _ = route_top_k(logits, k, self.scoring)
        moved = ~jnp.any(experts[:, :, None] == plain[:, None, :], axis=-1)
        self.sow("counters", "moe_bias", jnp.stack(
            [jnp.sum(moved.reshape(b, -1), axis=1, dtype=jnp.int32),
             jnp.full((b,), moved.size // b, jnp.int32)], axis=1))
        return experts, weights, probs, load

    def _count(self, experts, first, count, order, placed, b, per_row):
        """Sow the layer's counters: per row of the batch, its assignments
        to each held expert, their sum, those among them that found no room
        in the buffer, and all it made."""
        local = (experts - first).reshape(b, per_row)
        here = (local >= 0) & (local < count)
        to_each = jnp.sum(jax.nn.one_hot(jnp.where(here, local, count),
                                         count + 1, dtype=jnp.int32),
                          axis=1)[:, :count]
        placed_of = jnp.zeros((b,), jnp.int32).at[order // per_row].add(
            placed.astype(jnp.int32))
        held = jnp.sum(to_each, axis=1)
        self.sow("counters", "moe", jnp.concatenate(
            [to_each, held[:, None], (held - placed_of)[:, None],
             jnp.full((b, 1), per_row, jnp.int32)], axis=1))
