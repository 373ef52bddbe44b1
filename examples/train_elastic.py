"""Elastic data-parallel training — the dynamic-training flagship.

Reference: ``example/dynamic-training/train_resnet.py`` + ``run.sh``.  Run
under the launcher; add/remove worker hosts by editing the host_worker file
while the job runs:

    printf "worker-0\\nworker-1\\n" > /tmp/host_worker
    python -m dt_tpu.launcher.launch -n 2 -H /tmp/host_worker \
        --elastic-training-enabled True -- \
        python examples/train_elastic.py --network resnet20 \
        --num-classes 10 --image-shape 32,32,3 --batch-size 64 \
        --num-epochs 20
    echo "worker-2" >> /tmp/host_worker   # +1 worker at next epoch boundary

Per Lin et al. (arXiv:1904.12043): the GLOBAL batch and LR schedule stay
fixed; per-worker batch = global/num_workers recomputed on every membership
change (``train_resnet.py:315-317,369-374``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import common  # noqa: E402


def main():
    ap = common.base_parser("elastic training")
    ap.set_defaults(kv_store="tpu_sync")
    args = ap.parse_args()
    image_shape = common.setup(args)

    import numpy as np
    from dt_tpu import data, parallel
    from dt_tpu.elastic.client import auto_client

    ctrl = auto_client()
    kv = parallel.create(args.kv_store)
    if ctrl is not None:
        kv.set_controller(ctrl)

    # deterministic shared dataset (swap for ImageRecordIter + .rec shards)
    rng = np.random.RandomState(1234)
    n = min(args.num_examples, 4096)
    x = rng.uniform(-1, 1, (n,) + image_shape).astype(np.float32)
    y = rng.randint(0, args.num_classes, n).astype(np.int32)

    def factory(num_parts, part_index, batch_size):
        it = data.NDArrayIter(x, y, batch_size=batch_size, shuffle=True,
                              num_parts=num_parts, part_index=part_index,
                              seed=args.seed)
        return data.ResizeIter(it, size=n // args.batch_size), None

    eit = data.ElasticDataIterator(factory, args.batch_size)
    train, val = eit.get_data_iterator(kv)
    steps = train.steps_per_epoch or 1
    mod = common.make_module(args, steps, kv)
    if ctrl is not None:
        mod.sync_mode = "host"  # CPU-process cluster; TPU pods use the mesh
    common.fit_elastic(args, mod, train, val, eit)
    import jax
    import logging
    dev = jax.devices()[0]
    logging.info("worker %s rank %d finished: step %d on %s (%s) "
                 "state sha256 %s", getattr(ctrl, "host", "local"), kv.rank,
                 int(mod.state.step), dev.platform, dev.device_kind,
                 common.state_digest(mod.state))


if __name__ == "__main__":
    main()
