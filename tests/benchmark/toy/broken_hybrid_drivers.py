"""The hybrid decoder's timed path, broken underneath: `correct` has to come
out false for each.  Copied beside the drivers of a temporary copy of the
benchmark (bench_toy.make_copy); no cell of the benchmark names them."""

from hybrid_drivers import HybridLMJob


class SlowDecayJob(HybridLMJob):
    """The scan's decay broken: the state forgets at half the rate the
    equations give (``a`` halved on its way into ``ops/ssm.py``
    ``ssd_scan``); everything else as published."""

    def __init__(self, cfg, traffic, chips, seed):
        from dt_tpu.ops import ssm
        scan = ssm.ssd_scan
        ssm.ssd_scan = lambda x, dt, a, b, c, **kw: scan(x, dt, 0.5 * a, b, c,
                                                         **kw)
        super().__init__(cfg, traffic, chips, seed)


class UnitResidualJob(HybridLMJob):
    """The residual multiplier left out (1 in place of the published
    0.22)."""

    def __init__(self, cfg, traffic, chips, seed):
        super().__init__({**cfg, "residual_multiplier": 1.0}, traffic, chips,
                         seed)
        self.cfg = cfg      # the reference's weights are drawn as published
