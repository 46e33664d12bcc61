"""Distributed STAR engine on a device mesh (shard_map over partitions).

The single-process :class:`repro.core.engine.StarEngine` validates protocol
semantics; this module is the *cluster* form — the shape that runs on real
hardware:

* database partitions sharded over a 1-D ``part`` mesh axis — one device is
  one paper "node" holding a contiguous block of ``ppn = P / n_nodes``
  primary partitions, plus (``secondary=True``) a PHYSICAL secondary copy
  of the previous node's block in home-major layout — the partial replica
  set is real state, not a modeling convention;
* **partitioned phase**: ``shard_map`` with NO collectives inside — each
  device runs its partitions' queues serially (H-Store semantics), exactly
  the paper's zero-coordination claim, verified by asserting the phase's
  HLO contains no collective ops.  The phase executes in ``n_slabs``
  chunks of queue slots and the committed op stream of each chunk SHIPS to
  the full replica (and the secondary homes) while the next chunk
  executes — the §5 in-phase op-stream overlap — so the replication fence
  waits only on the unshipped tail slab;
* **replication fence**: a ``psum`` barrier carrying the per-device commit
  counters — the §4.3 statistics exchange — reached with every slab but
  the tail already applied;
* **single-master phase**: the designated master executes cross-partition
  transactions on its full copy (no 2PC — the paper's core claim), then
  the write stream is scattered back to the partition owners AND the
  secondary homes with the Thomas write rule; index maintenance replays
  round-ordered on every partial copy.

Ordered secondary indexes (``indexes=[IndexSpec...]``) ride the same
machinery end-to-end: partition-sharded segments inside the shard_map
phase (local ``part_ids`` align global keys with local segments), the full
replica's segments updated by the slab replay, the single-master phase
executing on the full copy's segments — so the full five-transaction
TPC-C mix runs on the cluster runtime with ``replica_consistent()``
covering records and every index segment.

Beyond the mesh execution, the engine carries what the cluster runtime
(`repro.cluster`) needs for §4.5 fault tolerance: two-version snapshots at
the epoch fence (revert on failure — which also discards the in-flight
epoch's consumed stream slabs, tracked by a slab high-watermark so a
re-executed epoch applies each slab exactly once), node-granular memory
loss + donor-copy restore, surviving-secondary block restore, full-replica
rebuild from the partial set, and per-node commit / fence-wait telemetry.
Its ``run_epoch`` returns the same metric surface as
``StarEngine.run_epoch``, so ``service.TxnService`` drives either engine
unchanged.

Tests run the mesh on 1-8 forced CPU devices; ``chip_smoke.py --chips 4``
runs it on four TPU chips, and ``tests/test_tpu_compile.py`` compiles its
programs (:class:`MeshPrograms`) for a described v5e:2x2.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.baselines.cost_model import Network
from repro.changelog.log import ChangeLog
from repro.compat import shard_map
from repro.core import replication as repl
from repro.core.engine import (SM_STATS, EngineStats, check_kernel,
                               to_host, tree_nbytes)
from repro.core.partitioned import run_partitioned
from repro.core.phase_switch import PhaseController
from repro.core.single_master import run_single_master
from repro.obs import trace as obs
from repro.storage.index import IndexSpec, make_index


def _pad_pow2(tree, axis: int):
    """Pad a txn pytree to the next power of two along `axis` so epoch
    shapes stay stable across batches (no per-epoch recompilation)."""
    def pad(a):
        n = a.shape[axis]
        target = 1 << max(0, (n - 1).bit_length())
        if target == n:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, target - n)
        return np.pad(np.asarray(a), widths)
    return jax.tree.map(pad, tree)


class _ReplicaShip:
    """ChangeLog subscriber doing the physical replica shipping: each
    published slab device-transfers to the master's device (the §5
    network ship) and replays in order on the full replica, then — rolled
    home-major — onto the physical secondary homes; the single-master
    stream scatters back to the partition owners and secondary homes
    under the Thomas write rule, index rounds replaying on every partial
    copy.  Fires while the NEXT slab executes, so the fence only ever
    waits on the tail."""

    def __init__(self, eng):
        self.eng = eng

    def on_slab(self, log, info):
        eng = self.eng
        with obs.span("replica.replay_full", cat="replay",
                      epoch=info["epoch"], slab=info["slab"]):
            log_m = jax.device_put(log, eng._master_dev)
            eng.full_val, eng.full_tid, fidx = eng.prog.replay_full(
                eng.full_val, eng.full_tid, log_m, eng.full_idx)
            if eng.has_index:
                eng.full_idx = fidx
        if eng.secondary:
            with obs.span("replica.replay_secondary", cat="replay",
                          epoch=info["epoch"], slab=info["slab"]):
                eng.sec_val, eng.sec_tid, sidx = eng.prog.replay_sec(
                    eng.sec_val, eng.sec_tid, log, eng.sec_idx)
                if eng.has_index:
                    eng.sec_idx = sidx

    def on_master(self, stream):
        eng = self.eng
        with obs.span("replica.scatter_back", cat="replay"):
            slog = stream["log"]
            w = slog["write"].reshape(-1)
            rows = jax.device_put(
                jnp.where(w, slog["row"].reshape(-1), -1), eng._bcast)
            vals = jax.device_put(slog["val"].reshape(-1, eng.C), eng._bcast)
            tids = jax.device_put(slog["tid"].reshape(-1), eng._bcast)
            eng.part_val, eng.part_tid = eng.prog.scatter(
                eng.part_val, eng.part_tid, rows, vals, tids)
            if eng.secondary:
                eng.sec_val, eng.sec_tid = eng.prog.scatter_sec(
                    eng.sec_val, eng.sec_tid, rows, vals, tids)
            if eng.has_index:
                kb = jax.device_put(stream["kinds"], eng._bcast)
                db = jax.device_put(stream["delta"], eng._bcast)
                iwb = jax.device_put(slog["iwrite"], eng._bcast)
                tdb = jax.device_put(slog["tid"], eng._bcast)
                eng.part_idx = eng.prog.sm_idx_replay(
                    eng.part_idx, kb, db, iwb, tdb)
                if eng.secondary:
                    eng.sec_idx = eng.prog.sm_idx_replay_sec(
                        eng.sec_idx, kb, db, iwb, tdb)


class ClusterStarEngine:
    """f full replicas (the designated master's complete copies) + the
    node-sharded partial replicas: each node's contiguous ``ppn``-partition
    primary block plus the physical secondary copy of its predecessor's
    block (round-robin homes, matching ``ClusterConfig.partition_homes``)."""

    LEDGER_CAP = 4096              # committed-slab telemetry window

    def _roll_home(self, tree):
        """The ONE encoding of the home-major secondary layout: array
        row p holds partition (p - ppn) mod P, i.e. node m hosts node
        m-1's block (ClusterConfig.partition_homes round-robin).  Every
        site that materializes, resyncs, reloads, or checks the
        secondary copies goes through this shift — a collective permute
        of whole node blocks over the ``part`` axis."""
        return self.prog.ship_home(jax.device_put(tree, self._shard))

    def __init__(self, mesh, n_partitions: int, rows_per_partition: int,
                 n_cols: int = 10, init_val=None, max_rounds: int = 16,
                 iteration_ms: float = 10.0, adaptive_epoch: bool = False,
                 indexes: list[IndexSpec] | None = None,
                 net: Network | None = None, n_slabs: int = 4,
                 secondary: bool | None = None, kernel: str = "jnp"):
        assert "part" in mesh.axis_names
        # the engine's programs rely on compiler-propagated shardings
        # (GSPMD): a mesh with Explicit axes (jax.make_mesh's default)
        # would demand an out_sharding on every gather over the blocks
        mesh = Mesh(mesh.devices, mesh.axis_names,
                    axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        # "pallas" rides the fused kernels everywhere index maintenance /
        # OCC rounds run: the sharded partitioned phase, the single-master
        # phase on the full copy, and every partial-replica replay —
        # bit-identical results either way (interpret mode only)
        check_kernel(kernel)
        self.kernel = kernel
        self.mesh = mesh
        self.n_nodes = int(mesh.shape["part"])
        assert n_partitions % self.n_nodes == 0, \
            (n_partitions, self.n_nodes)
        self.ppn = n_partitions // self.n_nodes
        self.P, self.R, self.C = n_partitions, rows_per_partition, n_cols
        self.index_specs = list(indexes or [])
        self.has_index = bool(self.index_specs)
        self.net = net or Network()
        assert n_slabs >= 1, n_slabs
        self.n_slabs = n_slabs
        # physical secondary partial replicas need a second distinct home
        self.secondary = (self.n_nodes > 1 if secondary is None
                          else (secondary and self.n_nodes > 1))
        val = (jnp.asarray(init_val, jnp.int32) if init_val is not None
               else jnp.zeros((self.P, self.R, self.C), jnp.int32))
        tid = jnp.zeros((self.P, self.R), jnp.uint32)
        self._shard = NamedSharding(mesh, P("part"))
        self._bcast = NamedSharding(mesh, P())
        self.prog = MeshPrograms(mesh, n_partitions, rows_per_partition,
                                 n_cols, self.index_specs, max_rounds,
                                 kernel)
        # f=1 asymmetric replication, physically: the full replica lives on
        # the DESIGNATED MASTER's device only (node 0) — replicating it
        # across the mesh would execute the op replay and the whole
        # single-master phase redundantly on every device (N x the CPU for
        # f=1 semantics)
        self._master_dev = jax.sharding.SingleDeviceSharding(
            mesh.devices.flat[0])
        # partial replicas: partition-sharded primary copy
        self.part_val = jax.device_put(val, self._shard)
        self.part_tid = jax.device_put(tid, self._shard)
        # full replica (master's complete copy) — on the master node
        self.full_val = jax.device_put(val, self._master_dev)
        self.full_tid = jax.device_put(tid, self._master_dev)
        idx0 = [make_index(s, self.P) for s in self.index_specs]
        self.part_idx = jax.device_put(idx0, self._shard)
        self.full_idx = jax.device_put(idx0, self._master_dev)
        # physical secondary copies, home-major: array row p holds
        # partition (p - ppn) mod P, so node m's block holds the SECONDARY
        # copy of node (m-1)'s partitions (ClusterConfig.partition_homes
        # round-robin with replicas_per_partition=2)
        if self.secondary:
            self.sec_val = jax.device_put(self._roll_home(val),
                                          self._shard)
            self.sec_tid = jax.device_put(self._roll_home(tid),
                                          self._shard)
            self.sec_idx = jax.device_put(self._roll_home(idx0),
                                          self._shard)
        else:
            self.sec_val = self.sec_tid = None
            self.sec_idx = []
        self.epoch = 1
        self.max_rounds = max_rounds
        self.controller = PhaseController(e_ms=iteration_ms,
                                          adaptive=adaptive_epoch)
        self.stats = EngineStats()
        # per-node telemetry (fig12/fig13 skew): committed txns and modeled
        # fence wait (the slowest node sets the fence; everyone else waits)
        self.node_committed = np.zeros(self.n_nodes, np.int64)
        self.node_fence_wait_s = np.zeros(self.n_nodes)
        # the one ordered op stream: the engine PUBLISHES (slabs, master
        # stream, commit/revert) and every consumer subscribes — the
        # physical replica shipper first (stream order), then any sink
        # (WAL, materialized views) the runtime/service registers.  The
        # changelog owns the slab high-watermark (in-flight slabs the
        # subscribers consumed; a §4.5 revert discards them so a
        # re-executed epoch applies each slab exactly once) and the
        # committed slab ledger (bounded, explicit drop-oldest — tests
        # assert exactly-once application from it)
        self.changelog = ChangeLog(n_slabs, ledger_cap=self.LEDGER_CAP)
        self.changelog.subscribe(_ReplicaShip(self))
        # read-tier watermark: the fence epoch the committed snapshot
        # (``_snap``) corresponds to — 0 until the first commit
        self.committed_epoch = 0
        self._seq0 = jax.device_put(jnp.zeros((self.P,), jnp.uint32),
                                    self._shard)
        self._snap = self._state()

    # ------------------------------------------------------------------
    @property
    def _slab_hwm(self) -> int:
        """In-flight slabs the subscribers already consumed (changelog
        high-watermark; kept as a property for the runtime/tests)."""
        return self.changelog.slab_hwm

    @property
    def slab_ledger(self) -> list:
        """Committed (epoch, slab) ledger — owned by the changelog."""
        return self.changelog.ledger

    def committed_state(self):
        """(val, tid) of the committed full-replica snapshot — the seed
        state changelog subscribers (MVs, analytics) reset from."""
        return self._snap["full_val"], self._snap["full_tid"]

    def _slab_bounds(self, T: int):
        return self.changelog.slab_bounds(T)

    # ------------------------------------------------------------------
    def run_epoch(self, batch, ingest=None, commit=True,
                  abort_check=None) -> dict:
        """StarEngine-compatible epoch: slab-streamed partitioned phase
        (sharded, zero collectives; each slab's op stream ships to the
        replicas while the next slab executes), psum fence waiting only on
        the tail slab, single-master phase on the full copy, value +
        index-stream scatter-back, epoch fence + two-version snapshot.

        ingest: optional zero-arg callable overlapped with the partitioned
        phase's device execution (double-buffered host batch formation).
        commit=False runs the phases up TO the epoch fence but never
        commits — the cluster runtime uses it for an epoch whose fence a
        failed node will miss: everything the phases wrote (including the
        stream slabs the replicas already consumed, via the slab
        high-watermark) is discarded by the §4.5 revert.
        abort_check: optional callable(slab_idx) -> bool polled after each
        slab's execution dispatch; returning True at slab s kills the
        epoch mid-stream (a node died during the phase) with slabs
        0..s-1 already shipped: remaining slabs never execute or ship."""
        with obs.span("engine.epoch", "epoch", epoch=self.epoch) as sp:
            if not commit:
                # a failed node misses this epoch's fence: it re-executes
                # under the same number, so readers tell the two apart
                sp.set(doomed=True)
            m = self._run_epoch(batch, ingest, commit, abort_check)
            if "aborted_at_slab" not in m:
                sp.set(committed=m["committed_single"]
                       + m["committed_cross"], commit=commit)
        return m

    def _run_epoch(self, batch, ingest, commit, abort_check) -> dict:
        tr = obs.get_tracer()
        e = self.epoch
        epoch_u = jnp.uint32(self.epoch)
        with tr.span("engine.upload", "host", epoch=e) as sp:
            ptxn = jax.tree.map(jnp.asarray, _pad_pow2(batch["ptxn"], 1))
            cross = jax.tree.map(jnp.asarray, _pad_pow2(batch["cross"], 0))
            if tr.enabled:
                sp.set(bytes=tree_nbytes((ptxn, cross)))

        # ---- partitioned phase: slab-chained execution + streaming ------
        T = ptxn["row"].shape[1]
        bounds = self._slab_bounds(T)
        S = len(bounds) - 1
        t0 = time.perf_counter()
        pv, pt, pidx, seq = (self.part_val, self.part_tid, self.part_idx,
                             self._seq0)
        slab_logs, committed_chunks, counts = [], [], None
        aborted_at = None
        with tr.span("engine.partitioned", "phase", epoch=e, slabs=S):
            for s in range(S):
                slab = jax.tree.map(lambda a: a[:, bounds[s]:bounds[s + 1]],
                                    ptxn)
                with tr.span("cluster.slab_execute", cat="phase",
                             epoch=e, slab=s,
                             txns=bounds[s + 1] - bounds[s]):
                    pv, pt, pidx, seq, log, comm, extras = self.prog.part(
                        pv, pt, pidx, seq, slab, epoch_u)
                if s > 0:
                    # previous slab's stream ships while THIS slab executes
                    self.changelog.publish_slab(slab_logs[s - 1], self.epoch)
                slab_logs.append(log)
                committed_chunks.append(comm)
                counts = extras if counts is None else counts + extras
                if abort_check is not None and abort_check(s):
                    aborted_at = s
                    break
            t_ingest = 0.0
            if ingest is not None:   # overlap host ingest with device exec
                ti = time.perf_counter()
                with tr.span("service.ingest_overlap", "service", epoch=e):
                    ingest()
                t_ingest = time.perf_counter() - ti
            tb = time.perf_counter()
            with tr.span("engine.partitioned.wait", "wait", epoch=e):
                jax.block_until_ready(pv)
            t1 = time.perf_counter()
        t_part = max(t1 - t0 - t_ingest, t1 - tb)
        self.part_val, self.part_tid, self.part_idx = pv, pt, pidx

        if aborted_at is not None:
            # mid-stream death: the epoch can never commit; the caller
            # reverts, which discards the slabs already consumed
            return {"aborted_at_slab": aborted_at,
                    "slabs_executed": aborted_at + 1,
                    "slabs_consumed": self._slab_hwm}

        # ---- tail ship: the ONLY stream transfer the fence waits on -----
        with tr.span("fence.tail_ship", cat="fence", epoch=e, slab=S - 1):
            self.changelog.publish_slab(slab_logs[-1], self.epoch)
        plog = self.changelog.epoch_plog()
        p_committed = (committed_chunks[0] if S == 1 else
                       jnp.concatenate(committed_chunks, axis=1))

        # ---- stream byte attribution (the changelog's single source) ----
        vb = 0
        with tr.span("engine.accounting", "host", epoch=e, stream="part"):
            attr = self.changelog.attribute(batch, plog, self.has_index,
                                            lambda a: _pad_pow2(a, 1),
                                            epoch=e)
        vb_alt, slab_bytes, ib = (attr.value_bytes_alt, attr.slab_bytes,
                                  attr.index_op_bytes)
        ob = attr.total
        ob_head, ob_tail = attr.overlapped, attr.fence

        # ---- fence 1 (commit-statistics psum barrier) --------------------
        tf0 = time.perf_counter()
        with tr.span("engine.fence", "fence", which=1, epoch=e,
                     tail_bytes=ob_tail, overlapped_bytes=ob_head):
            with tr.span("fence.psum", "fence", epoch=e, tail_bytes=ob_tail):
                node_counts = self.prog.fence_barrier(
                    jnp.asarray(counts[:, 0], jnp.int32))
                n_single = int(node_counts[0])
            # modeled network: the tail slab drains inside the fence; the
            # head slabs shipped during execution and surface only as
            # un-hidden residue (paper: "negligible" — now measurable
            # instead of assumed)
            t_net1 = repl.fence_net_seconds(self.net, ob_tail, ob_head,
                                            t_part)
        t_fence1 = time.perf_counter()

        # ---- single-master phase on the full copy ------------------------
        # B from the RAW batch: padding turns an empty cross batch into 1-2
        # invalid lanes, which would run the full OCC program for nothing
        # (service batches always carry fixed non-zero lane counts)
        t0 = time.perf_counter()
        B = int(batch["cross"]["row"].shape[0])
        slog = None
        ib_sm = 0
        with tr.span("engine.single_master", "phase", epoch=e,
                     rounds=self.max_rounds if B else 0):
            if B > 0:
                with tr.span("engine.sm_flatten", "host", epoch=e):
                    flat_v = self.full_val.reshape(self.P * self.R, self.C)
                    flat_t = self.full_tid.reshape(self.P * self.R)
                fv, ft, out, sstats = self.prog.sm(flat_v, flat_t,
                                                   self.full_idx, cross,
                                                   epoch_u)
                with tr.span("engine.single_master.wait", "wait", epoch=e):
                    jax.block_until_ready(fv)
                self.full_val = fv.reshape(self.P, self.R, self.C)
                self.full_tid = ft.reshape(self.P, self.R)
                if self.has_index:
                    self.full_idx = out["index"]
                # publish the master stream: the subscriber value-replicates
                # the writes back to partition owners and secondary homes
                # (the device_put broadcast is the value-stream ship, §5)
                # and replays the index-op rounds on every partial copy
                slog = out["log"]
                self.changelog.publish_master(slog, kinds=cross["kind"],
                                              delta=cross["delta"], epoch=e)
                with tr.span("engine.accounting", "host", epoch=e,
                             stream="sm"):
                    if self.has_index:
                        ib_sm = repl.index_op_bytes(slog["iwrite"])
                    if "c_row_bytes" in batch:
                        cw = np.asarray(slog["write"])
                        crb = np.broadcast_to(
                            _pad_pow2(batch["c_row_bytes"], 0), cw.shape[1:])
                        vb = repl.wait_int(
                            repl.value_bytes(cw, crb[None]), e)
                    elif batch.get("row_bytes") is not None:
                        vb = repl.wait_int(repl.value_bytes(
                            np.asarray(slog["write"]),
                            batch["row_bytes"][None, None, :]), e)
                with tr.span("engine.readback", "host", epoch=e) as sp:
                    dev = {"sstats": {k: sstats[k] for k in SM_STATS
                                      if k in sstats},
                           "c_committed": out["committed"]}
                    sm_host = to_host(dev)
                    if tr.enabled:
                        sp.set(arrays=len(jax.tree.leaves(dev)),
                               bytes=tree_nbytes(sm_host))
                sstats = sm_host["sstats"]
                c_committed = sm_host["c_committed"]
                n_cross = int(sstats["committed"])
                starved = int(sstats["starved"])
                retries = int(sstats["retries"])
                aborts = int(sstats["user_aborts"])
                sm_skips = int(sstats.get("consume_skips", 0))
                sm_overflow = int(sstats.get("index_overflow", 0))
            else:
                n_cross = starved = retries = aborts = 0
                sm_skips = sm_overflow = 0
                c_committed = np.zeros(0, bool)
        t_sm = time.perf_counter() - t0

        # ---- fence 2: epoch boundary + two-version snapshot --------------
        # the fence's contract is "every outstanding stream applied": wait
        # for the tail replay and the value scatter-back HERE (their time
        # is fence time) — otherwise the master device's replay backlog
        # silently delays the NEXT epoch's partitioned phase
        tf2 = time.perf_counter()
        with tr.span("engine.fence", "fence", which=2, epoch=e,
                     commit=commit):
            with tr.span("fence.replay_drain", "wait", epoch=e):
                jax.block_until_ready((self.full_val, self.part_val))
            t_net2 = repl.fence_net_seconds(self.net, vb + ib_sm)
            with tr.span("engine.readback", "host", epoch=e) as sp:
                dev = {"p_committed": p_committed, "counts": counts}
                if self.has_index:
                    dev["p_cskip"] = plog["cskip"]
                    if B > 0:
                        dev["c_cskip"] = slog["cskip"]
                host = to_host(dev)
                if tr.enabled:
                    sp.set(arrays=len(jax.tree.leaves(dev)),
                           bytes=tree_nbytes(host))
            p_committed = host["p_committed"]                  # (P, T)
            node_c = p_committed.sum(1).reshape(self.n_nodes, -1).sum(1)
            # modeled fence wait: the slowest node's phase time sets the
            # fence; a node's own busy time is proxied by its committed
            # share
            cmax = int(node_c.max()) if node_c.size else 0
            wait = (t_part * (1.0 - node_c / cmax) if cmax > 0
                    else np.zeros(self.n_nodes))
            tau_p = tau_s = 0.0
            counts_h = host["counts"]
            n_skips = int(counts_h[:, 1].sum()) + sm_skips
            n_overflow = int(counts_h[:, 2].sum()) + sm_overflow
            # partitioned-phase user aborts count too (StarEngine parity)
            aborts += int(counts_h[:, 3].sum())
            if commit:
                self.snapshot_commit()
                self.epoch += 1
                self.node_committed += node_c
                self.node_fence_wait_s += wait
                self.controller.observe_fence_wait(float(wait.max()) * 1e3)
                self.controller.observe("partitioned", n_single, t_part)
                self.controller.observe("single", n_cross, t_sm,
                                        frac_cross=n_cross
                                        / max(n_cross + n_single, 1))
                tau_p, tau_s = self.controller.plan()
        t_fence2 = time.perf_counter()
        if commit:
            s = self.stats
            s.epochs += 1
            s.committed_single += n_single
            s.committed_cross += n_cross
            s.user_aborts += aborts
            s.consume_skips += n_skips
            s.index_overflow += n_overflow
            s.retries += retries
            s.part_time_s += t_part
            s.sm_time_s += t_sm
            s.sm_rounds += self.max_rounds if B > 0 else 0
            s.fences += 2
            s.fence_time_s += (t_fence1 - tf0) + (t_fence2 - tf2)
            s.fence_net_s += t_net1 + t_net2
            s.value_bytes += vb
            s.op_bytes_hybrid += ob
            s.value_bytes_if_not_hybrid += vb_alt
            s.index_op_bytes += ib + ib_sm
            s.op_bytes_overlapped += ob_head
            s.op_bytes_fence += ob_tail

        m = {"committed_single": n_single, "committed_cross": n_cross,
             "tau_p_ms": tau_p, "tau_s_ms": tau_s,
             "t_part_s": t_part, "t_sm_s": t_sm, "t_ingest_s": t_ingest,
             "t_fence1_s": t_fence1, "t_fence2_s": t_fence2,
             "t_fence_net_s": t_net1 + t_net2,
             "op_bytes_overlapped": ob_head, "op_bytes_fence": ob_tail,
             "slabs": S,
             "p_committed": p_committed, "c_committed": c_committed,
             "index_overflow": n_overflow,
             "starved": starved,
             "node_committed": node_c,
             "node_fence_wait_s": wait}
        if self.has_index:
            m["p_cskip"] = host["p_cskip"]                     # (P, T, K)
            m["c_cskip"] = (host["c_cskip"].any(0)
                            if B > 0 else None)                # (B_pad, K)
        return m

    # ------------------------------------------------------------------
    # two-version snapshots + node-granular state surgery (§4.5)
    # ------------------------------------------------------------------
    def _state(self):
        st = {"part_val": self.part_val, "part_tid": self.part_tid,
              "full_val": self.full_val, "full_tid": self.full_tid,
              "part_idx": self.part_idx, "full_idx": self.full_idx}
        if self.secondary:
            st.update({"sec_val": self.sec_val, "sec_tid": self.sec_tid,
                       "sec_idx": self.sec_idx})
        return st

    def _load_state(self, st):
        self.part_val, self.part_tid = st["part_val"], st["part_tid"]
        self.full_val, self.full_tid = st["full_val"], st["full_tid"]
        self.part_idx, self.full_idx = st["part_idx"], st["full_idx"]
        if self.secondary:
            self.sec_val, self.sec_tid = st["sec_val"], st["sec_tid"]
            self.sec_idx = st["sec_idx"]

    def snapshot_commit(self):
        self._snap = self._state()
        self.committed_epoch = self.epoch
        # the in-flight slabs are now committed state: the changelog
        # retires them into its ledger and fires on_commit (WAL sink, MV
        # stamping) inside the fence.  slabs_shipped counts COMMITTED
        # slabs only, so it stays consistent with the committed-epoch
        # byte split — warm-up and doomed epochs' ships land in
        # slabs_discarded instead
        shipped, dropped = self.changelog.commit(self.epoch)
        self.stats.slabs_shipped += shipped
        self.stats.ledger_dropped += dropped

    def revert_to_snapshot(self):
        """Discard the in-flight epoch on every replica (two-version
        records, §4.5.2) — including every stream slab the subscribers
        consumed mid-phase (changelog revert: the re-executed epoch
        re-publishes from slab 0 onto the reverted base, so each slab
        applies to committed state exactly once)."""
        self._load_state(self._snap)
        self.stats.slabs_discarded += self.changelog.revert(self.epoch)

    def node_slice(self, node: int) -> slice:
        return slice(node * self.ppn, (node + 1) * self.ppn)

    def sec_home(self, node: int) -> int:
        """The node holding the physical secondary copy of ``node``'s
        block (round-robin: the next node)."""
        return (node + 1) % self.n_nodes

    def read_views(self):
        """Committed snapshot views for the read tier's SnapshotCatalog —
        one per physical replica copy: the master's full copy (covers
        every partition, identity row mapping) and each node's hosted
        secondary block (home-major rolled layout: partition p lives at
        array row (p + ppn) mod P; node m's view covers node m-1's
        partitions).  Always the COMMITTED two-version snapshot, so an
        in-flight or reverted epoch is never visible to a read."""
        wm = self.changelog.watermark(self.committed_epoch)
        P = self.P
        views = [{
            "id": "full", "kind": "full", "node": 0,
            "epoch": self.committed_epoch, "watermark": wm,
            "cover": np.ones(P, bool),
            "row_of_partition": np.arange(P, dtype=np.int64),
            "val": self._snap["full_val"], "tid": self._snap["full_tid"],
            "idx": self._snap["full_idx"],
        }]
        if self.secondary:
            rop = (np.arange(P, dtype=np.int64) + self.ppn) % P
            for m in range(self.n_nodes):
                owner = (m - 1) % self.n_nodes
                cover = np.zeros(P, bool)
                cover[self.node_slice(owner)] = True
                views.append({
                    "id": f"sec{m}", "kind": "secondary", "node": m,
                    "epoch": self.committed_epoch, "watermark": wm,
                    "cover": cover, "row_of_partition": rop,
                    "val": self._snap["sec_val"],
                    "tid": self._snap["sec_tid"],
                    "idx": self._snap["sec_idx"],
                })
        return views

    @staticmethod
    def _scribble_tree(tree, sl):
        def scrib(a):
            junk = (jnp.uint32(0xDEAD) if a.dtype == jnp.uint32
                    else jnp.int32(-0x5A5A5A5).astype(a.dtype))
            return a.at[sl].set(junk)
        return jax.tree.map(scrib, tree)

    def scribble_node(self, node: int):
        """Simulate the node's memory dying with it: its primary partition
        block AND the secondary copy it hosted (of its predecessor's
        block), in BOTH the working state and the snapshot — so recovery
        is only correct if it really restores from a surviving source
        (secondary home, full replica, or disk)."""
        sl = self.node_slice(node)
        snap = dict(self._snap)
        names = ["part_val", "part_tid", "part_idx"]
        if self.secondary:
            names += ["sec_val", "sec_tid", "sec_idx"]
        for name in names:
            setattr(self, name, self._scribble_tree(getattr(self, name), sl))
            snap[name] = self._scribble_tree(snap[name], sl)
        self._snap = snap

    def scribble_full(self):
        """Simulate loss of every full replica (all f holders dead)."""
        sl = slice(None)
        snap = dict(self._snap)
        for name in ("full_val", "full_tid", "full_idx"):
            setattr(self, name, self._scribble_tree(getattr(self, name), sl))
            snap[name] = self._scribble_tree(snap[name], sl)
        self._snap = snap

    # -- recovery-time restores (all from the COMMITTED snapshot) --------
    def _restore_blocks(self, nodes, src_val_key: str, src_tid_key: str,
                        src_idx_key: str, src_slice_fn):
        """Rebuild the nodes' primary partition blocks (records + index
        segments) from a surviving source in the committed snapshot, make
        that the committed version everywhere, and resync the rejoining
        secondary homes.  (Recovery path: the copy goes through the host —
        source and destination live on different devices.)  Returns the
        bytes copied into the blocks."""
        snap = dict(self._snap)
        pv = np.asarray(snap["part_val"]).copy()
        pt = np.asarray(snap["part_tid"]).copy()
        sv = np.asarray(snap[src_val_key])
        st = np.asarray(snap[src_tid_key])
        pidx = jax.tree.map(lambda a: np.asarray(a).copy(),
                            snap["part_idx"])
        sidx = jax.tree.map(np.asarray, snap[src_idx_key])
        copied = 0
        for n in nodes:
            sl = self.node_slice(n)
            ssl = src_slice_fn(n)
            pv[sl] = sv[ssl]
            pt[sl] = st[ssl]
            copied += pv[sl].nbytes + pt[sl].nbytes
            for pi, si in zip(pidx, sidx):
                for k in ("key", "prow", "tid"):
                    pi[k][sl] = si[k][ssl]
                    copied += pi[k][sl].nbytes
        snap["part_val"] = jax.device_put(jnp.asarray(pv), self._shard)
        snap["part_tid"] = jax.device_put(jnp.asarray(pt), self._shard)
        snap["part_idx"] = jax.device_put(
            jax.tree.map(jnp.asarray, pidx), self._shard)
        self._snap = snap
        self._resync_secondary()
        self._load_state(self._snap)
        return copied

    def restore_nodes_from_full(self, nodes):
        """§4.5.3 case-1/3 donor copy: rebuild the nodes' partition blocks
        from the (surviving) full replica's committed snapshot, then make
        that the nodes' own committed version.  Returns the bytes copied."""
        return self._restore_blocks(nodes, "full_val", "full_tid",
                                    "full_idx", self.node_slice)

    def restore_blocks_from_secondary(self, nodes):
        """The actual surviving-copy restore (replaces the old
        committed-snapshot stand-in): a dead node's primary block is
        rebuilt from the PHYSICAL secondary copy its neighbor holds —
        the copy itself, not an un-scribbled convenience alias.  Block
        n's secondary copy sits in its sec home's slice rows.  Returns the
        bytes copied."""
        assert self.secondary, "no physical secondary replicas configured"
        return self._restore_blocks(
            nodes, "sec_val", "sec_tid", "sec_idx",
            lambda n: self.node_slice(self.sec_home(n)))

    def rebuild_full_from_partials(self):
        """§4.5.3 case 2: every partition still has a live partial copy
        but no full replica survives — re-replicate a full copy by
        gathering the committed partial set (the bootstrap all-gather,
        again), index segments included.  Returns the bytes copied."""
        snap = dict(self._snap)
        fv = jax.device_put(jnp.asarray(snap["part_val"]), self._master_dev)
        ft = jax.device_put(jnp.asarray(snap["part_tid"]), self._master_dev)
        snap["full_val"], snap["full_tid"] = fv, ft
        snap["full_idx"] = jax.device_put(
            jax.tree.map(jnp.asarray, snap["part_idx"]), self._master_dev)
        self._snap = snap
        self._resync_secondary()
        self._load_state(self._snap)
        return tree_nbytes((fv, ft, snap["full_idx"]))

    def _resync_secondary(self):
        """§4.5.3 catch-up for rejoining secondary homes: rebuild the
        home-major secondary arrays from the committed primary set (the
        recovering node re-copies its hosted block)."""
        if not self.secondary:
            return
        snap = dict(self._snap)
        snap["sec_val"] = jax.device_put(
            self._roll_home(snap["part_val"]), self._shard)
        snap["sec_tid"] = jax.device_put(
            self._roll_home(snap["part_tid"]), self._shard)
        snap["sec_idx"] = jax.device_put(
            self._roll_home(snap["part_idx"]), self._shard)
        self._snap = snap

    def load_committed(self, val, tid, indexes=None):
        """§4.5.1 UNAVAILABLE reload: install a recovered committed state
        (checkpoint + replayed logs, index segments included) on every
        replica."""
        val = jnp.asarray(val, jnp.int32).reshape(self.P, self.R, self.C)
        tid = jnp.asarray(tid, jnp.uint32).reshape(self.P, self.R)
        self.part_val = jax.device_put(val, self._shard)
        self.part_tid = jax.device_put(tid, self._shard)
        self.full_val = jax.device_put(val, self._master_dev)
        self.full_tid = jax.device_put(tid, self._master_dev)
        if self.has_index:
            # a recovered state MUST carry index arrays — silently keeping
            # the (scribbled) in-memory segments would commit garbage
            assert indexes is not None, \
                "recovery returned no index arrays for an index engine " \
                "(checkpoint predates index durability?)"
            assert len(indexes) == len(self.index_specs), \
                (len(indexes), len(self.index_specs))
            idx = [{k: jnp.asarray(ix[k]) for k in ("key", "prow", "tid")}
                   for ix in indexes]
            self.part_idx = jax.device_put(idx, self._shard)
            self.full_idx = jax.device_put(idx, self._master_dev)
        if self.secondary:
            self.sec_val = jax.device_put(self._roll_home(val),
                                          self._shard)
            self.sec_tid = jax.device_put(self._roll_home(tid),
                                          self._shard)
            self.sec_idx = jax.device_put(self._roll_home(self.part_idx),
                                          self._shard)
        # the reloaded state is the LAST COMMITTED epoch's — the in-flight
        # epoch (self.epoch) re-executes on top of it after recovery.
        # Deliberately NOT a changelog.commit: a commit here would hand
        # the WAL sink epoch-(e-1) state labeled epoch e, and epoch e's
        # index ops (replayed strictly-after e_c) would be lost on the
        # next recovery.  The stream history is gone — subscribers reset
        # from the recovered arrays instead.
        self._snap = self._state()
        self.committed_epoch = self.epoch - 1
        self.changelog.reset_from_state(val, tid, self.committed_epoch)

    # ------------------------------------------------------------------
    def consistent(self) -> bool:
        """Partial replicas (sharded) == full replica (master copy) ==
        physical secondary copies (rolled home-major layout), records AND
        every index segment."""
        pv = np.asarray(self.part_val)
        fv = np.asarray(self.full_val)
        pt = np.asarray(self.part_tid)
        ft = np.asarray(self.full_tid)
        if not (np.array_equal(pv, fv) and np.array_equal(pt, ft)):
            return False
        for pi, fi in zip(self.part_idx, self.full_idx):
            for k in ("key", "prow", "tid"):
                if not np.array_equal(np.asarray(pi[k]), np.asarray(fi[k])):
                    return False
        if self.secondary:
            if not (np.array_equal(
                        np.asarray(self._roll_home(self.part_val)),
                        np.asarray(self.sec_val))
                    and np.array_equal(
                        np.asarray(self._roll_home(self.part_tid)),
                        np.asarray(self.sec_tid))):
                return False
            for pi, si in zip(self.part_idx, self.sec_idx):
                for k in ("key", "prow", "tid"):
                    if not np.array_equal(
                            np.asarray(self._roll_home(pi[k])),
                            np.asarray(si[k])):
                        return False
        return True

    def partitioned_phase_has_no_collectives(self, batch) -> bool:
        """Compile-time proof of the §4.1 zero-coordination claim."""
        ptxn = jax.tree.map(jnp.asarray, _pad_pow2(batch["ptxn"], 1))
        T = ptxn["row"].shape[1]
        bounds = self._slab_bounds(T)
        slab = jax.tree.map(lambda a: a[:, bounds[0]:bounds[1]], ptxn)
        txt = self.prog.part.lower(self.part_val, self.part_tid,
                                   self.part_idx, self._seq0, slab,
                                   jnp.uint32(1)).compile().as_text()
        return not any(op in txt for op in
                       ("all-reduce(", "all-gather(", "collective-permute(",
                        "all-to-all(", "reduce-scatter("))


class MeshPrograms:
    """The cluster engine's jitted programs over one ``part`` mesh.  They
    are built from the mesh and the table shapes alone, with no device
    state, so the same programs can be lowered for a described topology
    (``tests/test_tpu_compile.py``).

    Each program is a named function, so that it runs, and shows in a
    device trace, as ``jit_cluster_<what>``, a name no other program has.
    """

    def __init__(self, mesh, n_partitions: int, rows_per_partition: int,
                 n_cols: int, index_specs, max_rounds: int, kernel: str):
        N = int(mesh.shape["part"])
        ppn, R, C = n_partitions // N, rows_per_partition, n_cols
        has_index = bool(index_specs)
        pspec = P("part")
        # block m -> node m+1: each node's block moves to its secondary
        # home over the interconnect (ICI on a TPU slice)
        home_perm = [(m, (m + 1) % N) for m in range(N)]

        def cluster_ship_home(tree):
            return jax.tree.map(
                lambda a: jax.lax.ppermute(a, "part", home_perm), tree)

        self.ship_home = jax.jit(shard_map(
            cluster_ship_home, mesh, in_specs=(pspec,), out_specs=pspec))

        def cluster_partitioned(val, tid, index, seq, ptxn, epoch):
            # NO collectives inside: single-partition txns need none (§4.1).
            # part_ids map this block's local segment rows to their global
            # partition ids so index maintenance lands on the right keys.
            pid = jax.lax.axis_index("part")
            part_ids = pid * ppn + jnp.arange(ppn, dtype=jnp.int32)
            v, t, out, stats = run_partitioned(
                val, tid, ptxn, epoch, seq0=seq,
                index=index if has_index else None, part_ids=part_ids,
                kernel=kernel)
            idx = out.get("index", index)
            extras = jnp.stack([stats["committed"],
                                stats["consume_skips"],
                                stats["index_overflow"],
                                stats["user_aborts"]])[None]
            return (v, t, idx, out["seq"], out["log"], out["committed"],
                    extras)

        txn_spec = {k: P("part") for k in
                    ("valid", "row", "kind", "delta", "user_abort")}
        idx_spec = [{k: P("part") for k in ("key", "prow", "tid")}
                    for _ in index_specs]
        log_keys = ["row", "val", "tid", "write", "kind", "delta"]
        if has_index:
            log_keys += ["iwrite", "cskip"]
        log_spec = {k: P("part") for k in log_keys}
        self.part = jax.jit(shard_map(
            cluster_partitioned, mesh,
            in_specs=(pspec, pspec, idx_spec, pspec, txn_spec, P()),
            out_specs=(pspec, pspec, idx_spec, pspec, log_spec, pspec,
                       pspec)))

        def cluster_fence(commit_counts):
            # §4.3: nodes exchange commit statistics; the psum is the barrier
            return jax.lax.psum(commit_counts, "part")

        self.fence_barrier = jax.jit(shard_map(
            cluster_fence, mesh, in_specs=(P("part"),), out_specs=P()))

        # single-master phase runs on the master's device only (its full
        # copy lives there) — no 2PC, no cross-device coordination during
        # execution; the write stream ships back through the scatters
        def cluster_single_master(v, t, idx, txns, epoch):
            return run_single_master(v, t, txns, epoch,
                                     max_rounds=max_rounds,
                                     index=idx if has_index else None,
                                     kernel=kernel)

        self.sm = jax.jit(cluster_single_master)

        def cluster_scatter_back(part_val, part_tid, rows, vals, tids):
            """Apply the master's write stream to the partition owners:
            each device filters the global stream to its own row range."""
            pid = jax.lax.axis_index("part")
            lo = pid * ppn * R
            local = (rows >= lo) & (rows < lo + ppn * R)
            lrows = jnp.where(local, rows - lo, -1)
            v, t, _ = repl.thomas_apply(part_val.reshape(ppn * R, C),
                                        part_tid.reshape(ppn * R),
                                        lrows, vals, tids)
            return v.reshape(ppn, R, C), t.reshape(ppn, R)

        self.scatter = jax.jit(shard_map(
            cluster_scatter_back, mesh,
            in_specs=(pspec, pspec, P(), P(), P()),
            out_specs=(pspec, pspec)))

        def cluster_scatter_back_sec(sec_val, sec_tid, rows, vals, tids):
            """Same stream, delivered to each block's SECONDARY home: node
            m's sec block holds node (m-1)'s partitions (home-major)."""
            pid = jax.lax.axis_index("part")
            lo = jnp.mod(pid - 1, N) * ppn * R
            local = (rows >= lo) & (rows < lo + ppn * R)
            lrows = jnp.where(local, rows - lo, -1)
            v, t, _ = repl.thomas_apply(sec_val.reshape(ppn * R, C),
                                        sec_tid.reshape(ppn * R),
                                        lrows, vals, tids)
            return v.reshape(ppn, R, C), t.reshape(ppn, R)

        self.scatter_sec = jax.jit(shard_map(
            cluster_scatter_back_sec, mesh,
            in_specs=(pspec, pspec, P(), P(), P()),
            out_specs=(pspec, pspec)))

        # ordered op-stream replay onto the full replica — jitted once; an
        # eager form here would retrace EVERY slab (host-bound).  One slab
        # = one jitted replay of its slot range (records + index ops).
        def cluster_replay_full(v, t, log, idx):
            return repl.replay_partitioned(v, t, log,
                                           idx if has_index else None,
                                           kernel=kernel)

        self.replay_full = jax.jit(cluster_replay_full)

        def cluster_replay_sec(v, t, log, idx):
            # the permute IS the ship: each block's ordered stream moves
            # to its secondary home, which replays it on the copy it hosts
            # (node m holds node m-1's partitions)
            pid = jax.lax.axis_index("part")
            part_ids = jnp.mod((pid - 1) * ppn
                               + jnp.arange(ppn, dtype=jnp.int32),
                               n_partitions)
            v, t, idx_out = repl.replay_partitioned(
                v, t, cluster_ship_home(log),
                idx if has_index else None, part_ids=part_ids,
                kernel=kernel)
            return v, t, (idx_out if has_index else idx)

        self.replay_sec = jax.jit(shard_map(
            cluster_replay_sec, mesh,
            in_specs=(pspec, pspec, pspec, idx_spec),
            out_specs=(pspec, pspec, idx_spec)))

        if has_index:
            def cluster_index_replay(idx, kinds, delta, iwrite, tids):
                pid = jax.lax.axis_index("part")
                part_ids = pid * ppn + jnp.arange(ppn, dtype=jnp.int32)
                return repl.replay_index_rounds(idx, kinds, delta, iwrite,
                                                tids, part_ids=part_ids,
                                                kernel=kernel)

            def cluster_index_replay_sec(idx, kinds, delta, iwrite, tids):
                pid = jax.lax.axis_index("part")
                part_ids = jnp.mod(
                    pid * ppn + jnp.arange(ppn, dtype=jnp.int32) - ppn,
                    n_partitions)
                return repl.replay_index_rounds(idx, kinds, delta, iwrite,
                                                tids, part_ids=part_ids,
                                                kernel=kernel)

            bspecs = (idx_spec, P(), P(), P(), P())
            self.sm_idx_replay = jax.jit(shard_map(
                cluster_index_replay, mesh, in_specs=bspecs,
                out_specs=idx_spec))
            self.sm_idx_replay_sec = jax.jit(shard_map(
                cluster_index_replay_sec, mesh, in_specs=bspecs,
                out_specs=idx_spec))
