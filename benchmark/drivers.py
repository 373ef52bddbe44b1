"""The jobs a cell can drive: the program's entry points, as a user calls them.

A configuration's file names one of these under ``driver``.  Each builds the
``Module`` the way the program's own example does, hands ``fit`` the
benchmark's ``Feed`` and callbacks, and maps the plain reference's weights
onto the program's parameter tree (names only: the numbers are the
benchmark's, made from the seed).  No training step is defined here.

The runner asks a job for four things and knows nothing else about it:
``make_state`` (weights from the seed), ``first_steps`` (the steps `correct`
compares), ``run_window`` (the measured window: completion times of its
steps) and ``release``.  A job that is not one ``Module`` in one process (a
launcher with workers) is a new module beside this one that answers the same
four.
"""

import gc
import os
import resource
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dtype(cfg):
    import jax.numpy as jnp
    return jnp.dtype(cfg["dtype"])


class WindowDone(Exception):
    """Raised by the clock out of ``fit`` when the window has closed: the
    steps the loop has already queued (two: one dispatched, one prefetched)
    would otherwise run after it, 0.6 s of every LM run."""


class Clock:
    """The batch-end callback kept with the benchmark (the Speedometer's
    pattern): ``fit`` calls it once ``_flush_metric`` has fetched what that
    step's program hands the host for the metric (its per-row statistics;
    its logits for a metric without a device form), so the host clock read
    here is a completion time.  The
    window starts at the ``warm_steps``-th completion (or at ``t_start``
    where there is none) and ends at the first completion at or after
    ``seconds``; every step between the two is in it.  In a traced run the
    profiler runs over the window's last ``trace["last_s"]`` seconds, from a
    step edge to the closing one, and is stopped once the closing time has
    been read: stopping it costs seconds of host time (3.7 s for twenty
    ResNet-50 steps), which would otherwise be a stall inside the window.
    Beside each completion
    time it keeps what says why a step was slow: the loop thread's and the
    process's CPU time, the thread's involuntary context switches, the
    seconds the garbage collector ran, and the seconds in which a second
    thread that only sleeps 5 ms at a time did not run either (the whole
    process, or the machine under it, stood still)."""

    def __init__(self, feed, seconds, warm_steps, t_start, trace=None):
        self.feed, self.seconds, self.warm = feed, seconds, warm_steps
        self.times, self.marks = [], []
        self.seen = 0
        self.trace = trace          # None or dict(dir, last_s)
        self.trace_span = None      # [t_start, t_stop, first step, last step]
        self.stop_trace_s = 0.0     # what stopping the profiler cost
        self.done = False
        self.gc_s, self._gc_t0 = 0.0, None
        gc.callbacks.append(self._gc)
        self.frozen = []            # (start, seconds) the heartbeat missed
        self._beat = threading.Thread(target=self._heartbeat, daemon=True)
        self._beat.start()
        if warm_steps == 0:
            self._mark(t_start)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def _heartbeat(self):
        while not self.done:
            t0 = time.perf_counter()
            time.sleep(0.005)
            late = time.perf_counter() - t0 - 0.005
            if late > 0.05:
                self.frozen.append((t0, late))

    def _mark(self, now):
        self.times.append(now)
        self.marks.append((time.thread_time(), time.process_time(),
                           resource.getrusage(
                               resource.RUSAGE_THREAD).ru_nivcsw, self.gc_s))

    def close(self):
        self.done = True
        self._beat.join()
        if self._gc in gc.callbacks:
            gc.callbacks.remove(self._gc)

    def __call__(self, param):
        now = time.perf_counter()
        self.seen += 1
        if self.seen < self.warm or self.done:
            return
        self._mark(now)
        k = len(self.times) - 1     # steps completed inside the window
        closing = k > 0 and now - self.times[0] >= self.seconds
        if self.trace is not None and self.trace_span is None and not closing \
                and now - self.times[0] >= self.seconds - self.trace["last_s"]:
            self._start_trace(k)
        if closing:
            self.done = True
            self.stop_trace()
            self.feed.stop()
            raise WindowDone

    def _start_trace(self, k):
        import jax
        # the host tracers off: at level 1 or 2 a ResNet-50 step's input
        # transfer alone writes 270k host events (316 MB for ten steps)
        # and the traced steps take 0.8 s each instead of 0.1
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(self.trace["dir"], profiler_options=opts)
        self.trace_span = [time.perf_counter(), None, k, None]

    def stop_trace(self):
        import jax
        if self.trace_span is not None and self.trace_span[1] is None:
            self.trace_span[1] = self.times[-1]
            self.trace_span[3] = len(self.times) - 1
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_trace_s = time.perf_counter() - t0

    def slow_steps(self, top=5, factor=1.5):
        """The slowest steps that took over ``factor`` times the median
        step, each with where its time went: [(step, seconds into the
        window, wall s, loop-thread CPU s, process CPU s, involuntary
        switches, collector s, s the whole process stood still)]."""
        walls = [b - a for a, b in zip(self.times, self.times[1:])]
        if not walls:
            return []
        median = sorted(walls)[len(walls) // 2]
        slow = sorted((i for i, w in enumerate(walls) if w > factor * median),
                      key=lambda i: -walls[i])[:top]
        def frozen(i):
            return sum(d for t, d in self.frozen
                       if self.times[i] <= t < self.times[i + 1])
        return [(i + 1, self.times[i] - self.times[0], walls[i])
                + tuple(b - a for a, b in zip(self.marks[i],
                                              self.marks[i + 1]))
                + (frozen(i),) for i in sorted(slow)]


class Job:
    """What the runner needs of a job; subclasses fill in the program."""

    metric_names = ()   # what fit() is asked to report; the last is the loss

    def __init__(self, cfg, traffic, chips):
        self.cfg, self.traffic, self.chips = cfg, traffic, chips
        self.mod = None

    def sample_shape(self):
        raise NotImplementedError

    def program_tree(self, ref):
        """The reference's tree of leaves, under the program's names."""
        raise NotImplementedError

    def fit(self, feed, callbacks):
        raise NotImplementedError

    # -- shared ---------------------------------------------------------

    def make_state(self, ref_init, key):
        """One jitted call: the reference's ``init`` -> the program's
        ``TrainState``, born replicated on the module's mesh."""
        import jax
        import jax.numpy as jnp
        from dt_tpu.parallel import mesh as mesh_lib
        from dt_tpu.training.train_state import TrainState
        mod = self.mod
        shape, dtype = self.sample_shape()
        want = jax.eval_shape(
            lambda: mod.model.init(
                {"params": jax.random.PRNGKey(0),
                 "dropout": jax.random.PRNGKey(1)},
                jnp.zeros(shape, dtype), training=False))
        stats_shape = want.get("batch_stats", {})

        def build(k):
            params = self.program_tree(ref_init(k, self.cfg))
            got = jax.tree_util.tree_map(lambda a: a.shape, params)
            exp = jax.tree_util.tree_map(lambda a: a.shape,
                                         dict(want["params"]))
            if got != exp:
                raise ValueError("the reference's weights do not fit the "
                                 f"program's tree:\n{got}\n!=\n{exp}")
            # stored in the type the program stores that leaf in
            params = jax.tree_util.tree_map(
                lambda a, w: a.astype(w.dtype), params, dict(want["params"]))
            # running mean 0 and variance 1, as published
            stats = jax.tree_util.tree_map_with_path(
                lambda path, a: (jnp.ones if path[-1].key == "var"
                                 else jnp.zeros)(a.shape, a.dtype),
                stats_shape)
            return TrainState.create(mod.model.apply, params, mod.tx, stats)

        self._ref_init = ref_init
        make = jax.jit(build,
                       out_shardings=mesh_lib.replicate_sharding(mod.mesh))
        mod.state = make(key)
        return mod.state

    def first_gradient(self, state, params0):
        """The first gradient as the optimizer got it, from its state after
        one step."""
        import jax
        opt = self.cfg["optimizer"]
        inner = state.opt_state.inner if hasattr(state.opt_state, "inner") \
            else state.opt_state
        if opt["name"] == "sgd":
            # mom_1 = -lr * (g + wd * w_0)
            return jax.tree_util.tree_map(
                lambda m, w: -m / opt["learning_rate"]
                - opt["weight_decay"] * w, inner.mom, params0)
        if opt["name"] == "adam":
            # m_1 = (1 - beta1) * g
            return jax.tree_util.tree_map(
                lambda m: m / (1.0 - opt["beta1"]), inner.a)
        raise ValueError(f"no first_gradient for {opt['name']!r}")

    def _initial(self, key):
        return self.program_tree(self._ref_init(key, self.cfg))

    def first_gradient_host(self, key, state):
        """The first gradient on the host, from the state after one step;
        one jitted call that makes the initial weights again rather than
        keeping them."""
        import jax
        return jax.device_get(jax.jit(
            lambda k, st: self.first_gradient(st, self._initial(k)))(
                key, state))

    def param_change_host(self, key, state):
        """The parameters' change since ``init``, on the host."""
        import jax
        import jax.numpy as jnp
        return jax.device_get(jax.jit(
            lambda k, st: jax.tree_util.tree_map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                st.params, self._initial(k)))(key, state))

    # -- what the runner asks for ------------------------------------------

    def first_steps(self, feed, key, steps):
        """The job's first ``steps`` steps, each one ``fit`` call of one step
        through the window's own feed, object and compiled program: each
        step's loss as ``fit`` reports it, the first gradient (from the
        optimizer's state after one step) and the parameters' change after
        the last, both on the host."""
        import jax
        program = {"losses": []}
        seen = []
        for i in range(steps):
            seen.clear()
            self.fit(feed.arm(1), [lambda p: seen.append(
                dict(p.eval_metric.get_name_value())[self.metric_names[-1]])])
            program["losses"].append(float(seen[-1]))
            if i == 0:
                program["first_gradient"] = self.first_gradient_host(
                    key, self.mod.state)
        program["param_change"] = self.param_change_host(key, self.mod.state)
        jax.block_until_ready(self.mod.state)
        return program

    def run_window(self, feed, seconds, trace=None):
        """The measured window: one ``fit`` call through the same object,
        ended by the clock.  Returns the clock: ``times`` are the completion
        times of the window's steps, the first being its start."""
        import jax
        clock = Clock(feed, seconds, self.traffic["warm_steps"],
                      time.perf_counter(), trace)
        try:
            self.fit(feed.arm(None), [clock])
        except WindowDone:
            pass
        finally:
            clock.close()
        jax.block_until_ready(self.mod.state)
        clock.stop_trace()
        return clock

    def release(self):
        """Free the program's state: the reference runs in its place."""
        self.mod.state = None

    def memory_stats(self):
        """What the runtime says of the fullest chip's memory."""
        import jax
        stats = [d.memory_stats() or {}
                 for d in jax.local_devices()[:self.chips]]
        return max(stats, key=peak_bytes)


def peak_bytes(stats):
    """Peak bytes a device held: the allocator's peak of live buffers plus
    the peak the runtime reserved for the loaded programs' temporaries.  On
    the v5e the two do not overlap (PERF.md section 7): ``peak_bytes_in_use``
    reads the weights, optimizer state and inputs (0.6 GB for ResNet-50 at
    batch 256) and does not move when a program that needs 9.0 GB of
    activations is loaded and run; ``peak_bytes_reserved`` does."""
    return stats.get("peak_bytes_in_use", 0) \
        + stats.get("peak_bytes_reserved", 0)


class ImageJob(Job):
    """``examples/common.py`` (``make_module``, ``fit``) -> ``Module.fit``,
    flag for flag what ``examples/train_imagenet.py`` passes."""

    metric_names = ("accuracy", "cross-entropy")

    def __init__(self, cfg, traffic, chips, seed):
        super().__init__(cfg, traffic, chips)
        sys.path.insert(0, os.path.join(REPO, "examples"))
        import common
        from dt_tpu import parallel
        opt = cfg["optimizer"]
        self.common = common
        self.args = common.base_parser("benchmark").parse_args([
            "--network", cfg["network"],
            "--num-classes", str(cfg["num_classes"]),
            "--image-shape", ",".join(str(v) for v in cfg["image_shape"]),
            "--batch-size", str(traffic["batch"]),
            "--dtype", cfg["dtype"], "--optimizer", opt["name"],
            "--lr", str(opt["learning_rate"]), "--mom", str(opt["momentum"]),
            "--wd", str(opt["weight_decay"]), "--num-epochs", "1",
            "--benchmark", "1", "--seed", str(seed % (2 ** 31 - 64)),
            # the Speedometer stays silent: the benchmark keeps its own clock
            "--disp-batches", str(10 ** 9)])
        common.setup(self.args)
        kv = parallel.create(self.args.kv_store)
        # the learning-rate steps sit at epochs 30, 60, 90 of ImageNet
        self.mod = common.make_module(
            self.args, self.args.num_examples // traffic["batch"], kv)

    def sample_shape(self):
        return ((self.traffic["batch"],) + tuple(self.cfg["image_shape"]),
                _dtype(self.cfg))

    def cast(self, data):
        return data.astype(_dtype(self.cfg))

    def program_tree(self, ref):
        def bn(p):
            return {"scale": p["g"], "bias": p["b"]}
        tree = {"Conv_0": {"kernel": ref["stem"]["w"]},
                "BatchNorm_0": bn(ref["stem"]["bn"]),
                "Dense_0": {"kernel": ref["fc"]["w"], "bias": ref["fc"]["b"]}}
        for i, blk in enumerate(ref["blocks"]):
            out = {}
            for j, n in enumerate(("1", "2", "3") + (("d",) * ("wd" in blk))):
                out[f"Conv_{j}"] = {"kernel": blk["w" + n]}
                out[f"BatchNorm_{j}"] = bn(blk["bn" + n])
            tree[f"BottleneckV1_{i}"] = out
        return tree

    def fit(self, feed, callbacks):
        self.common.fit(self.args, self.mod, feed, None,
                        batch_end_callback=callbacks)


class LMJob(Job):
    """``TransformerLM`` through ``Module.fit`` on one device, the way
    ``chip_smoke.py`` stage B and the tests drive a language model (the
    repository's LM example writes a step of its own, so it is no entry)."""

    metric_names = ("cross-entropy",)

    def __init__(self, cfg, traffic, chips, seed):
        super().__init__(cfg, traffic, chips)
        import jax
        from dt_tpu import config as dt_config, models
        from dt_tpu.parallel import mesh as mesh_lib
        from dt_tpu.training import Module
        dt_config.maybe_force_cpu()
        opt = dict(cfg["optimizer"])
        model = models.TransformerLM(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["n_embd"],
            num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
            max_len=cfg["n_positions"], seq_parallel=traffic["seq_parallel"],
            dtype=_dtype(cfg))
        self.mod = Module(
            model, optimizer=opt.pop("name"),
            optimizer_params={**opt, "multi_precision":
                              cfg["dtype"] == "bfloat16"},
            mesh=mesh_lib.make_mesh(devices=jax.local_devices()[:chips]),
            seed=seed % (2 ** 31 - 64))

    def sample_shape(self):
        return (self.traffic["batch"], self.traffic["seq_len"]), np.int32

    def cast(self, data):
        return data

    def program_tree(self, ref):
        def ln(p):
            return {"scale": p["g"], "bias": p["b"]}
        tree = {"embed": {"embedding": ref["wte"]}, "pos_embed": ref["wpe"],
                "LayerNorm_0": ln(ref["ln_f"]),
                "lm_head": {"kernel": ref["head"]}}
        for i, blk in enumerate(ref["blocks"]):
            tree[f"block{i}"] = {
                "LayerNorm_0": ln(blk["ln_1"]),
                "MultiHeadAttention_0": {"qkv": {"kernel": blk["qkv"]},
                                         "proj": {"kernel": blk["proj"]}},
                "LayerNorm_1": ln(blk["ln_2"]),
                "mlp_in": {"kernel": blk["fc"], "bias": blk["fc_b"]},
                "mlp_out": {"kernel": blk["out"], "bias": blk["out_b"]}}
        return tree

    def fit(self, feed, callbacks):
        self.mod.fit(feed, eval_metric="ce", num_epoch=1,
                     batch_end_callback=callbacks)

