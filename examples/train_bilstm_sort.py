"""Bi-LSTM sequence sorting — the reference's bi-lstm-sort example.

Reference: ``example/bi-lstm-sort/sort_io.py`` + ``lstm_sort.py``: feed
a sequence of random tokens, supervise each output position with the
SORTED sequence — a pure sequence-to-sequence transduction that needs
both directions of context (position k of the sorted output depends on
the whole input), which is why the reference uses a bidirectional LSTM.
TPU-first shape: the framework's fused-scan
:func:`dt_tpu.ops.rnn.bidirectional_lstm` (one ``lax.scan`` a
direction) runs under ONE jit step; tokens embed, the bi-LSTM
encodes, a shared dense head scores every position.

    python examples/train_bilstm_sort.py --epochs 12
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--num-examples", type=int, default=4096)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from dt_tpu.config import maybe_force_cpu
    maybe_force_cpu()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from dt_tpu import data
    from dt_tpu.ops import losses, rnn

    rng = np.random.RandomState(args.seed)
    xs = rng.randint(0, args.vocab,
                     (args.num_examples, args.seq_len)).astype(np.int32)
    ys = np.sort(xs, axis=1).astype(np.int32)

    E, H, L = 32, args.hidden, args.layers
    key = jax.random.PRNGKey(args.seed)
    k_emb, k_f, k_b, k_out = jax.random.split(key, 4)

    def bi_weights(k):
        # layer 0 consumes the E-dim embedding; upper layers consume the
        # 2H fwd/bwd concat (bidirectional_lstm's cuDNN-style stacking)
        ws = rnn.init_lstm_weights(k, 1, E, H)
        for layer in range(1, L):
            k, sub = jax.random.split(k)
            ws += rnn.init_lstm_weights(sub, 1, 2 * H, H)
        return ws

    params = {
        "embed": 0.1 * jax.random.normal(k_emb, (args.vocab, E)),
        "fwd": bi_weights(k_f),
        "bwd": bi_weights(k_b),
        "w_out": 0.1 * jax.random.normal(k_out, (2 * H, args.vocab)),
        "b_out": jnp.zeros((args.vocab,)),
    }
    tx = optax.adam(args.lr)
    opt = tx.init(params)

    def forward(p, toks):
        emb = p["embed"][toks]                        # (B, S, E)
        b = toks.shape[0]
        h0 = jnp.zeros((2 * L, b, H))
        # rnn ops are time-major (T, B, *) like the reference's fused op
        outs, _, _ = rnn.bidirectional_lstm(
            jnp.swapaxes(emb, 0, 1), h0, h0, p["fwd"], p["bwd"])
        outs = jnp.swapaxes(outs, 0, 1)               # (B, S, 2H)
        return outs @ p["w_out"] + p["b_out"]         # (B, S, V)

    @jax.jit
    def step(p, opt, xb, yb):
        def loss_of(p):
            logits = forward(p, xb)
            return losses.softmax_cross_entropy(
                logits.reshape(-1, args.vocab), yb.reshape(-1))
        loss, grads = jax.value_and_grad(loss_of)(p)
        upd, opt = tx.update(grads, opt, p)
        return optax.apply_updates(p, upd), opt, loss

    n_val = args.num_examples // 8
    it = data.NDArrayIter(xs[n_val:], ys[n_val:],
                          batch_size=args.batch_size, shuffle=True,
                          seed=args.seed, last_batch_handle="discard")
    for epoch in range(args.epochs):
        loss = None
        for bt in it:
            params, opt, loss = step(params, opt, jnp.asarray(bt.data),
                                     jnp.asarray(bt.label))
        if epoch % 3 == 0 or epoch == args.epochs - 1:
            print(f"epoch {epoch}: loss={float(loss):.4f}", flush=True)

    pred = np.asarray(jnp.argmax(forward(params, jnp.asarray(xs[:n_val])),
                                 -1))
    tok_acc = float((pred == ys[:n_val]).mean())
    seq_acc = float((pred == ys[:n_val]).all(axis=1).mean())
    print(f"val token_acc={tok_acc:.3f} seq_acc={seq_acc:.3f}")
    assert tok_acc > 0.9, "bi-LSTM failed to learn sorting"
    return 0


if __name__ == "__main__":
    sys.exit(main())
