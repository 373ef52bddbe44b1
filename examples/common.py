"""Shared example plumbing.

Reference: ``example/image-classification/common/fit.py`` — argparse surface
(--network, --batch-size, --lr, --lr-factor, --lr-step-epochs, --num-epochs,
--kv-store, --model-prefix, --load-epoch, --disp-batches, --benchmark) and
the fit-loop wiring.  Zero-egress note: datasets must already be on disk
(.rec via ``dt_tpu.data.ImageRecordIter``); ``--benchmark 1`` runs on
synthetic data like the reference's benchmark mode.
"""

from __future__ import annotations

import argparse
import logging
import os

import jax.numpy as jnp
import numpy as np


def base_parser(description: str) -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--network", default="resnet50")
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--num-examples", type=int, default=1281167)
    ap.add_argument("--image-shape", default="224,224,3")
    ap.add_argument("--batch-size", type=int, default=128,
                    help="GLOBAL batch size (split across workers)")
    ap.add_argument("--num-epochs", type=int, default=10)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--lr-factor", type=float, default=0.1)
    ap.add_argument("--lr-step-epochs", default="30,60,90")
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--mom", type=float, default=0.9)
    ap.add_argument("--wd", type=float, default=1e-4)
    ap.add_argument("--warmup-epochs", type=int, default=0)
    ap.add_argument("--kv-store", default="local")
    ap.add_argument("--model-prefix", default=None)
    ap.add_argument("--load-epoch", type=int, default=None)
    ap.add_argument("--disp-batches", type=int, default=20)
    ap.add_argument("--benchmark", type=int, default=0)
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="microbatches per update (grad_req='add' "
                         "analog; peak activation HBM ~1/N)")
    ap.add_argument("--remat", type=int, default=0,
                    help="per-block rematerialization (memory mirror, "
                         "MXNET_BACKWARD_DO_MIRROR analog) for models "
                         "that support it")
    ap.add_argument("--data-train", default=None, help=".rec file")
    ap.add_argument("--data-val", default=None, help=".rec file")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--seed", type=int, default=0)
    return ap


def setup(args):
    from dt_tpu.config import maybe_force_cpu
    maybe_force_cpu()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(message)s")
    return tuple(int(x) for x in args.image_shape.split(","))


def make_scheduler(args, steps_per_epoch: int):
    from dt_tpu import optim
    steps = [int(e) * steps_per_epoch
             for e in args.lr_step_epochs.split(",") if e]
    return optim.MultiFactorScheduler(
        steps=steps, factor=args.lr_factor, base_lr=args.lr,
        warmup_steps=args.warmup_epochs * steps_per_epoch)


def make_data(args, image_shape, kv):
    """Build (train, val) iterators: .rec files if given, else synthetic
    benchmark batches; sharded by kv rank/num_workers."""
    from dt_tpu import data
    per_worker = max(args.batch_size // kv.num_workers, 1)
    if args.data_train and os.path.exists(args.data_train):
        # thread-pool decode inside ImageRecordIter + background batch
        # assembly: together they keep a TPU-rate consumer fed
        # (reference: OMP decode + PrefetcherIter)
        train = data.PrefetchingIter(data.ImageRecordIter(
            args.data_train, image_shape, per_worker, shuffle=True,
            num_parts=kv.num_workers, part_index=kv.rank,
            dtype=args.dtype, seed=args.seed))
        val = None
        if args.data_val and os.path.exists(args.data_val):
            val = data.ImageRecordIter(args.data_val, image_shape,
                                       per_worker, dtype=args.dtype)
        steps = args.num_examples // args.batch_size
        return data.ResizeIter(train, steps), val
    # synthetic (benchmark mode)
    nb = max(args.num_examples // args.batch_size, 1) if args.benchmark \
        else 50
    train = data.SyntheticImageIter(image_shape, args.num_classes,
                                    per_worker, num_batches=nb,
                                    seed=args.seed, dtype=args.dtype)
    return train, None


def make_module(args, steps_per_epoch: int, kv=None):
    from dt_tpu import models
    from dt_tpu.training import Module
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    kwargs = {}
    if getattr(args, "remat", 0):
        kwargs["remat"] = True  # resnets/transformer support per-block
    try:
        model = models.create(args.network, num_classes=args.num_classes,
                              dtype=dtype, **kwargs)
    except TypeError:
        if "remat" in kwargs:
            raise SystemExit(
                f"--remat is not supported by '{args.network}' (per-block "
                f"rematerialization exists for the resnet families and "
                f"transformer_lm)")
        raise
    sched = make_scheduler(args, steps_per_epoch)
    mod = Module(model, optimizer=args.optimizer,
                 optimizer_params={"learning_rate": sched,
                                   "momentum": args.mom,
                                   "weight_decay": args.wd,
                                   "multi_precision":
                                       args.dtype == "bfloat16"},
                 kvstore=kv if kv is not None else args.kv_store,
                 seed=args.seed,
                 grad_accum=getattr(args, "grad_accum", 1))
    return mod


def fit(args, mod, train, val, batch_end_callback=()):
    """``batch_end_callback``: extra per-batch callbacks, run before the
    Speedometer (whose auto-reset clears the running metric)."""
    from dt_tpu.training import callbacks, checkpoint
    cbs = list(batch_end_callback) + [
        callbacks.Speedometer(args.batch_size, args.disp_batches,
                              num_workers_fn=lambda: mod.kv.num_workers)]
    epoch_cbs = []
    if args.model_prefix:
        epoch_cbs.append(callbacks.do_checkpoint(args.model_prefix))
    begin = 0
    if args.load_epoch is not None and args.model_prefix:
        first = train.next().data
        train.reset()
        mod.init_params(first)
        mod.state = checkpoint.load_checkpoint(args.model_prefix,
                                               args.load_epoch, mod.state)
        begin = args.load_epoch + 1
    mod.fit(train, eval_data=val, eval_metric=["acc", "ce"],
            num_epoch=args.num_epochs, begin_epoch=begin,
            batch_end_callback=cbs, epoch_end_callback=epoch_cbs or None)
    return mod


def state_digest(state) -> str:
    """sha256 over the parameters and BN statistics: workers of one
    synchronous job end bit-identical, and this is how they show it."""
    import hashlib
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(
            jax.device_get((state.params, state.batch_stats))):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def fit_elastic(args, mod, train, val, elastic_data_iterator):
    """fit() with the elastic re-shard hook wired
    (reference ``example/dynamic-training`` fit path)."""
    from dt_tpu.training import callbacks
    cbs = [callbacks.Speedometer(args.batch_size, args.disp_batches,
                                 num_workers_fn=lambda: mod.kv.num_workers)]
    epoch_cbs = []
    if args.model_prefix:
        epoch_cbs.append(callbacks.do_checkpoint(args.model_prefix))
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            batch_end_callback=cbs, epoch_end_callback=epoch_cbs or None,
            elastic_data_iterator=elastic_data_iterator)
    return mod
