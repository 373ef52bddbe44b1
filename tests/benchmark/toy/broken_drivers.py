"""The rehearsal's timed path, broken underneath: `correct` has to come out
false for each.  Copied beside the drivers of a temporary copy of the
benchmark (bench_toy.make_copy); no cell of the benchmark names them."""

from drivers import LMJob


class FrozenLMJob(LMJob):
    """A step that returns its state unchanged."""

    def fit(self, feed, callbacks):
        state = self.mod.state   # the CPU does not donate: still valid
        super().fit(feed, callbacks)
        self.mod.state = state


class _HalfRows:
    def __init__(self, feed):
        self._feed = feed

    def __getattr__(self, name):
        return getattr(self._feed, name)

    def next(self):
        b = self._feed.next()
        half = b.data.shape[0] // 2
        return type(b)(b.data[:half], b.label[:half], 0)


class HalfBatchLMJob(LMJob):
    """A step that leaves out half of the batch."""

    def fit(self, feed, callbacks):
        super().fit(_HalfRows(feed), callbacks)
