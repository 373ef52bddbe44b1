"""Mesh lifecycle across membership changes — the multi-host data plane.

The reference rebuilt its ps-lite world the same way: a membership change
re-runs the ADD_NODE/BARRIER dance and every node adopts the new ring
(``ps-lite/src/van.cc:269-315``); the worker re-binds its executors at
the epoch boundary (``python/mxnet/module/base_module.py:503-549``).

SURVEY.md §5.8/§7 "hard parts": XLA/GSPMD assumes a fixed device set, so a
membership change means tearing down and re-initializing the
``jax.distributed`` runtime with the new host set, rebuilding the mesh, and
resharding the training state from a host-RAM snapshot.  This module owns
that dance; the elastic Scheduler/WorkerClient own the *decision* (who is in
the job).

On one host (or the CPU test mesh) ``rebuild`` degenerates to re-creating
the local mesh and re-placing state — exercised by tests; the
``jax.distributed`` branch runs on real pods where each worker process owns
one host's chips.

Mitigations from SURVEY.md §7 applied here:
- epoch-boundary only (caller's contract),
- snapshot in host RAM before teardown (``snapshot_state``),
- the persistent compilation cache keyed by world size amortizes the
  recompile (``Module`` places it via
  ``dt_tpu.config.enable_compilation_cache``, which also zeroes the
  min-compile-time threshold so small rebuilt programs are cached too).
"""

from __future__ import annotations

import logging
from typing import Any, List, Optional

import jax
import numpy as np

from dt_tpu.parallel import mesh as mesh_lib

logger = logging.getLogger("dt_tpu.elastic")


def snapshot_state(state: Any) -> Any:
    """Pull a (possibly sharded) pytree fully to host RAM (numpy).

    Leaves sharded ACROSS processes (ZeRO/FSDP state in a multi-host
    world) are not locally fetchable — ``device_get`` raises on
    non-addressable shards — so those gather via
    ``multihost_utils.process_allgather`` (a collective: every process
    must reach this snapshot, which the epoch-boundary contract
    guarantees).  Caught by the 2-process x 4-device ZeRO test."""
    # Drain every queued program that writes these buffers BEFORE the
    # gather collectives hit the wire: the caller's last train step can
    # still be executing when this dispatches (the block_until_ready
    # gotcha, collective edition), and its in-flight psums then
    # interleave with the allgather ops on the SAME gloo tcp pairs in
    # thread-scheduling order — which differs across ranks under CPU
    # contention, desyncing the pair framing (gloo EnforceNotMet
    # ``op.preamble.length <= op.nbytes``, observed at the 4-process
    # lifecycle's remove boundary and cascading into peer SIGABRTs).
    live = [x for x in jax.tree_util.tree_leaves(state)
            if isinstance(x, jax.Array)]
    if live:
        jax.block_until_ready(live)
    def pull(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from jax.experimental import multihost_utils
            return np.asarray(
                multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(jax.device_get(x))
    return jax.tree_util.tree_map(pull, state)


def restore_state(host_state: Any, mesh, shardings: Any = None) -> Any:
    """Re-place a host snapshot onto a (new) mesh.

    ``shardings``: optional pytree of per-leaf ``NamedSharding`` matching
    ``host_state`` for model-parallel layouts; default replicates every leaf
    (the DP case).

    Multi-process placement is COLLECTIVE-FREE: every process holds the
    full leaf (``snapshot_state`` allgathers, so blobs are bit-identical
    across ranks by contract) and each device's shard is sliced locally
    via ``make_array_from_callback``.  ``jax.device_put`` of a numpy
    value onto a non-addressable sharding instead runs a
    ``broadcast_one_to_all`` psum per leaf just to assert cross-process
    equality — a gloo round-trip per leaf that, under CPU contention,
    can interleave with neighbouring collectives on the same tcp pairs
    and desync the pair framing (observed as ``gloo::EnforceNotMet
    op.preamble.length <= op.nbytes`` killing the 4-process lifecycle
    test's joiner mid-rebuild).  The equality assert moves into the
    contract: feed every rank the SAME blob (a rank restoring a
    different value now diverges silently instead of tripping jax's
    device_put check — the snapshot path guarantees it)."""
    def put(x, s):
        if getattr(s, "is_fully_addressable", True):
            return jax.device_put(x, s)
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, s, lambda idx: arr[idx])
    if shardings is None:
        rep = mesh_lib.replicate_sharding(mesh)
        return jax.tree_util.tree_map(lambda x: put(x, rep), host_state)
    return jax.tree_util.tree_map(put, host_state, shardings)


class MeshManager:
    """Owns the distributed runtime + mesh for one worker process."""

    def __init__(self, coordinator_address: Optional[str] = None,
                 local_device_count: Optional[int] = None):
        self.coordinator_address = coordinator_address
        self.local_device_count = local_device_count
        self._initialized = False
        # CPU collectives impl (gloo/mpi) parked while in a solo world —
        # restored when a multi-process world re-forms (see initialize)
        self._saved_cpu_collectives: Optional[str] = None
        self.mesh = None

    def initialize(self, num_processes: int = 1, process_id: int = 0,
                   coordinator_address: Optional[str] = None):
        """Join the distributed world (no-op single-process).

        Real pods: every worker calls this with its rank and the coordinator
        (rank-0 host) address — the ``jax.distributed`` analog of ps-lite's
        scheduler rendezvous (``van.cc:95-185``).  ``coordinator_address``
        overrides the constructor's (the coordinator can move when
        membership changes remove the old rank-0 host)."""
        if coordinator_address is not None:
            self.coordinator_address = coordinator_address
        if num_processes > 1:
            if not self.coordinator_address:
                raise ValueError(
                    "multi-process world needs a coordinator_address; "
                    "refusing to build a local-only mesh that would silently "
                    "skip cross-host gradient averaging")
            if self._saved_cpu_collectives:
                # growing back from a solo world: restore the collectives
                # impl the solo rebuild parked, BEFORE the new backend
                # builds (gradient psums would otherwise stay local-only)
                jax.config.update("jax_cpu_collectives_implementation",
                                  self._saved_cpu_collectives)
                self._saved_cpu_collectives = None
            jax.distributed.initialize(
                coordinator_address=self.coordinator_address,
                num_processes=num_processes, process_id=process_id)
            self._initialized = True
        else:
            # Rebuilding down to a SOLO world: park a CPU collectives
            # impl (gloo/mpi belong to a multi-process world; restored on
            # the next multi-process initialize, where a regrown world
            # without it would skip cross-host gradient averaging) and
            # build the solo backend with local collectives only.
            impl = jax.config.jax_cpu_collectives_implementation
            if impl is not None:
                self._saved_cpu_collectives = impl
                jax.config.update("jax_cpu_collectives_implementation",
                                  None)
        self.mesh = mesh_lib.make_mesh()
        return self.mesh

    def depart(self, state: Any) -> None:
        """A REMOVED worker's exit path: participate in the final
        collective snapshot (survivors' ``rebuild`` gathers cross-process
        ZeRO/FSDP shards — a collective the old world must fully attend,
        see :func:`snapshot_state`), then leave the world.  Call this
        instead of bare ``teardown`` whenever the training state may be
        sharded across processes; with fully-addressable state it
        degenerates to a local copy + teardown."""
        if self._initialized and jax.process_count() > 1:
            snapshot_state(state)  # result unused; the collective matters
        self.teardown()

    def teardown(self, lost_coordinator: bool = False):
        """Leave the world.  ``lost_coordinator=True`` skips the orderly
        ``jax.distributed.shutdown`` handshake (it talks to the — dead —
        rank-0 host) and only drops local client state.

        Scope note (tests/jaxdist_worker_4p.py): jax's coordination
        service FATALLY terminates attached peers once it detects the
        leader's death, so this flag only helps in the narrow window
        before detection.  The robust coordinator-loss recovery is the
        restart path: survivor processes restart and re-form a smaller
        world from the epoch-end host snapshot under a new coordinator
        (the ps-lite scheduler was a single point of failure the same
        way; SURVEY §5.3)."""
        if self._initialized:
            if not lost_coordinator:
                jax.distributed.shutdown()
            else:
                # drop the local client/service WITHOUT the coordinator
                # round-trip (client.shutdown() handshakes with the dead
                # rank 0 and blocks); jax.distributed.initialize refuses
                # to run twice unless this state is cleared.  The
                # global_state fields are jax-private and shift across
                # releases — this path is best-effort by design, so a
                # layout mismatch degrades to a warning instead of
                # turning coordinator-loss teardown into an AttributeError
                try:
                    from jax._src import distributed as _jdist
                    st = _jdist.global_state
                    if st.preemption_sync_manager is not None:
                        st.preemption_sync_manager = None
                    st.client = None
                    if st.service is not None:
                        try:
                            st.service.shutdown()
                        except Exception:  # best effort: world is dead
                            pass
                        st.service = None
                    st.coordinator_address = None
                except (ImportError, AttributeError) as e:
                    logger.warning(
                        "jax._src.distributed.global_state layout changed "
                        "(%s); skipping best-effort client teardown — "
                        "re-initialize may require a process restart", e)
            # the XLA client caches the old world's device topology; drop
            # it so the next initialize() builds a client for the NEW world
            # (without this, jax.devices() keeps showing removed hosts'
            # devices and collectives hang)
            import jax.extend.backend as jex_backend
            jex_backend.clear_backends()
            self._initialized = False
        self.mesh = None

    def rebuild(self, state: Any, num_processes: int, process_id: int,
                coordinator_address: Optional[str] = None):
        """Membership changed: snapshot -> teardown -> re-init with the new
        world -> reshard.  Returns (new_mesh, restored_state).

        ``coordinator_address``: the NEW world's coordinator (rank-0 host
        after the change — the old one may have been removed).

        The reference's equivalent is ``updateNumWorker`` rewriting node
        groups in place (``postoffice.cc:71-187``); GSPMD cannot mutate a
        live mesh, so the world is rebuilt — acceptable at epoch granularity
        (the same boundary the reference restricts changes to)."""
        host_state = snapshot_state(state)
        self.teardown()
        mesh = self.initialize(num_processes, process_id,
                               coordinator_address)
        restored = restore_state(host_state, mesh)
        logger.info("mesh rebuilt: %d device(s), world=%d rank=%d",
                    mesh.devices.size, num_processes, process_id)
        return mesh, restored
