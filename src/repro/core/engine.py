"""STAR engine: phase-switched epochs over the storage subsystem (§3-§5).

One engine instance models the cluster: the master view (the designated full
replica) plus a backup replica kept consistent purely through the replication
streams — value replication (Thomas write rule, out-of-order) from the
single-master phase and ordered operation replication from the partitioned
phase (hybrid strategy, §5).  State lives in two ``storage.StorageEngine``
instances (array-resident tables + ordered secondary indexes, two-version
records); index maintenance replays through the same per-round/per-slot
batches the executors installed, so ``replica_consistent()`` verifying
bit-equality at each fence covers indexes as well as records.

The replication fence is no longer free: ``_fence`` pushes the epoch's
stream bytes through the ``baselines.cost_model.Network`` envelope and
reports the modeled inter-node lag as ``t_fence_net_s`` (paper §7.6: TPC-C
saturates the NIC at 4 nodes).

Fault tolerance: ``inject_failure``/``recover`` drive the §4.5 machinery —
revert to the last committed epoch via the two-version records, classify the
failure case, re-master partitions, catch up via Thomas-rule apply.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines.cost_model import Network
from repro.changelog.log import ChangeLog
from repro.core import replication as repl
from repro.core.fault import ClusterConfig, make_recovery_plan
from repro.core.partitioned import run_partitioned
from repro.core.phase_switch import PhaseController
from repro.core.single_master import run_single_master
from repro.obs import trace as obs
from repro.storage import IndexSpec, StorageEngine


# why kernel="pallas" is refused on a TPU backend: each fused kernel hits
# a construct the Pallas TPU lowering rejects (compiled for a described
# v5e at TPC-C widths)
PALLAS_TPU_BLOCKERS = (
    "the OCC lock build scatter-mins (unimplemented primitive: scatter-min)",
    "the OCC install scatters (unimplemented primitive: scatter)",
    "scan_window loads its scalar-prefetch refs as vectors (SMEM holds "
    "scalars only)",
    "index_merge's (1, capP) and (1, Kd) blocks break the (8, 128) tiling "
    "rule",
)


def check_kernel(kernel: str) -> None:
    """Validate an engine's ``kernel`` choice at construction.  The fused
    Pallas kernels run in interpret mode only; on a TPU backend they would
    fail deep inside the first compile, so refuse them here instead of
    falling back to the jnp path or the interpreter."""
    if kernel not in ("jnp", "pallas"):
        raise ValueError(f"kernel must be 'jnp' or 'pallas', not {kernel!r}")
    if kernel == "pallas" and jax.default_backend() == "tpu":
        raise NotImplementedError(
            "kernel='pallas' does not lower for TPU: "
            + "; ".join(PALLAS_TPU_BLOCKERS) + ". Use kernel='jnp'.")


@dataclass
class EngineStats:
    epochs: int = 0
    committed_single: int = 0
    committed_cross: int = 0
    user_aborts: int = 0
    consume_skips: int = 0          # Delivery districts skipped (stale scan)
    index_overflow: int = 0         # live index keys dropped at capacity
    retries: int = 0
    fences: int = 0
    value_bytes: int = 0
    op_bytes_hybrid: int = 0
    value_bytes_if_not_hybrid: int = 0
    index_op_bytes: int = 0         # index-maintenance ops on the op stream
    op_bytes_overlapped: int = 0    # shipped DURING the partitioned phase
    op_bytes_fence: int = 0         # the unshipped tail the fence waits on
    slabs_shipped: int = 0          # stream slabs applied to replicas
    slabs_discarded: int = 0        # in-flight slabs dropped by a revert
    ledger_dropped: int = 0         # slab-ledger entries aged out at the cap
    part_time_s: float = 0.0
    sm_time_s: float = 0.0
    sm_rounds: int = 0              # OCC rounds executed (kernel launches)
    fence_time_s: float = 0.0
    fence_net_s: float = 0.0


class _ReplicaReplay:
    """ChangeLog subscriber keeping the operation replica consistent: the
    ordered partitioned stream replays per slab (``replay_partitioned``),
    the single-master stream merges under the Thomas write rule with its
    round-ordered index ops (``replay_index_rounds``) — the same §5 hybrid
    strategy the engine used to hand-feed."""

    def __init__(self, eng):
        self.eng = eng

    def on_slab(self, log, info):
        eng = self.eng
        rv, rt, ri = eng._jit_replay(
            eng.replica_store.val, eng.replica_store.tid, log,
            eng.replica_store.indexes if eng.has_index else None,
            kernel=eng.kernel)
        eng.replica_store.val, eng.replica_store.tid = rv, rt
        if eng.has_index:
            eng.replica_store.indexes = ri

    def on_master(self, stream):
        eng = self.eng
        log = stream["log"]
        P, R, C = eng.P, eng.R, eng.C
        rflat_val = eng.replica_store.val.reshape(P * R, C)
        rflat_tid = eng.replica_store.tid.reshape(P * R)
        rv, rt, _ = eng._jit_thomas(rflat_val, rflat_tid, log)
        eng.replica_store.val = rv.reshape(P, R, C)
        eng.replica_store.tid = rt.reshape(P, R)
        if eng.has_index:
            eng.replica_store.indexes = eng._jit_replay_idx(
                eng.replica_store.indexes, stream["kinds"], stream["delta"],
                log["iwrite"], log["tid"], kernel=eng.kernel)

    def on_reset(self, val, tid, epoch):
        self.eng.replica_store.load_state(self.eng.store.snapshot)


class StarEngine:
    def __init__(self, n_partitions: int, rows_per_partition: int,
                 n_cols: int = 10, init_val=None, hybrid_replication=True,
                 max_rounds=16, cluster: ClusterConfig | None = None,
                 iteration_ms: float = 10.0,
                 indexes: list[IndexSpec] | None = None,
                 net: Network | None = None, adaptive_epoch: bool = False,
                 kernel: str = "jnp", strict_index: bool = False,
                 durability=None, n_slabs: int = 4):
        """kernel: "jnp" (reference executors) or "pallas" (fused OCC
        kernels, interpret mode only; refused on a TPU backend, see
        ``check_kernel``) — bit-identical results either way.
        strict_index: raise instead of counting when an ordered-index
        segment overflows its capacity (silently dropping the largest key
        otherwise — see storage.index.segment_apply).
        durability: optional ``db.wal.Durability`` — committed epochs
        append their value streams — and, with indexes attached, their
        ordered index-op streams — to per-worker write-ahead logs (flushed
        inside the commit fence) with checkpoints on a cadence;
        ``db.wal.recover_full`` then rebuilds the exact committed state
        (records AND index segments) from disk (§4.5.1's UNAVAILABLE
        case).
        n_slabs: the §5 op-stream overlap model — each epoch's partitioned
        stream ships in ``n_slabs`` chunks, the first ``n_slabs - 1``
        overlapped with execution and only the tail exposed at the fence
        (``n_slabs=1`` reproduces the old ship-everything-at-the-fence
        accounting)."""
        P, R, C = n_partitions, rows_per_partition, n_cols
        self.P, self.R, self.C = P, R, C
        check_kernel(kernel)
        self.kernel = kernel
        self.strict_index = strict_index
        self.store = StorageEngine(P, R, C, init_val=init_val,
                                   index_specs=indexes)
        self.replica_store = StorageEngine(P, R, C, init_val=init_val,
                                           index_specs=indexes)
        self.has_index = bool(indexes)
        self.epoch = 1
        # read-tier watermark: the fence epoch the committed snapshots
        # correspond to — 0 until the first epoch's commit fence
        self.committed_epoch = 0
        self.part_seq = jnp.zeros((P,), jnp.uint32)
        self.sm_last_tid = None
        self.hybrid = hybrid_replication
        self.max_rounds = max_rounds
        self.cluster = cluster or ClusterConfig(f=1, k=max(P, 1),
                                                n_partitions=P)
        self.controller = PhaseController(e_ms=iteration_ms,
                                          adaptive=adaptive_epoch)
        self.net = net or Network()
        assert n_slabs >= 1, n_slabs
        self.n_slabs = n_slabs
        self.durability = durability
        self.stats = EngineStats()
        self._jit_part = jax.jit(run_partitioned,
                                 static_argnames=("kernel",))
        self._jit_sm = jax.jit(run_single_master,
                               static_argnames=("max_rounds", "deterministic",
                                                "kernel"))
        self._jit_thomas = jax.jit(repl.thomas_apply_batch)
        self._jit_replay = jax.jit(repl.replay_partitioned,
                                   static_argnames=("kernel",))
        self._jit_replay_idx = jax.jit(repl.replay_index_rounds,
                                       static_argnames=("kernel",))
        # the one ordered op stream: the engine PUBLISHES (slabs, master
        # stream, commit/revert) and every consumer subscribes — the
        # operation replica first (stream order), then the WAL sink
        self.changelog = ChangeLog(n_slabs)
        self.changelog.subscribe(_ReplicaReplay(self))
        if durability is not None:
            from repro.db.wal import WalSink
            durability.attach(self.store.val, self.store.tid,
                              indexes=self.store.indexes
                              if self.has_index else None)
            self.changelog.subscribe(WalSink(
                durability, self.R, self.C,
                np.arange(self.P) % durability.n_workers,
                lambda: (self.store.val, self.store.tid,
                         self.store.indexes if self.has_index else None)))

    # -- dict views kept for callers/tests that read engine state --------
    @property
    def master(self):
        return {"val": self.store.val, "tid": self.store.tid}

    @property
    def replica(self):
        return {"val": self.replica_store.val, "tid": self.replica_store.tid}

    @property
    def snapshot(self):
        return {"val": self.store.snapshot["val"],
                "tid": self.store.snapshot["tid"]}

    # ------------------------------------------------------------------
    @staticmethod
    def _pad_axis(tree, axis: int):
        """Pad a txn pytree to the next power of two along `axis` so epoch
        shapes stay stable across batches (no per-epoch recompilation)."""
        def pad(a):
            n = a.shape[axis]
            target = 1 << max(0, (n - 1).bit_length())
            if target == n:
                return a
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, target - n)
            return np.pad(a, widths)
        return jax.tree.map(pad, tree)

    def run_epoch(self, batch, ingest=None) -> dict:
        """batch: output of ycsb/tpcc make_batch. Runs partitioned phase,
        fence, single-master phase, fence. Returns epoch metrics.

        ingest: optional zero-arg callable invoked while the partitioned
        phase executes on device (JAX dispatch is async) — the service layer
        hooks host-side batch formation for the *next* epoch here so ingest
        overlaps device execution (double buffering). Its host time is
        reported separately as ``t_ingest_s``."""
        tr = obs.get_tracer()
        t_ep0 = time.perf_counter()
        epoch_u = jnp.uint32(self.epoch)
        ptxn = jax.tree.map(jnp.asarray, self._pad_axis(batch["ptxn"], 1))
        cross = jax.tree.map(jnp.asarray, self._pad_axis(batch["cross"], 0))
        index = self.store.indexes if self.has_index else None

        # ---- partitioned phase (single-partition txns, no CC) ----------
        t0 = time.perf_counter()
        val, tidw, part_out, pstats = self._jit_part(
            self.store.val, self.store.tid, ptxn, epoch_u,
            self.part_seq, index, kernel=self.kernel)
        t_ingest = 0.0
        if ingest is not None:       # overlap host ingest with device exec
            ti = time.perf_counter()
            ingest()
            t_ingest = time.perf_counter() - ti
            tr.complete("service.ingest_overlap", "service", ti,
                        ti + t_ingest, epoch=self.epoch)
        tb = time.perf_counter()
        jax.block_until_ready(val)
        t1 = time.perf_counter()
        tr.complete("engine.partitioned", "phase", t0, t1,
                    epoch=self.epoch)
        # device-attributable time: when host ingest outlasts the device the
        # wall clock measures ingest, not the phase — don't let that deflate
        # the t_p estimate feeding Eq. 1-2 (t_ingest_s reports the overlap)
        t_part = max(t1 - t0 - t_ingest, t1 - tb)
        self.store.val, self.store.tid = val, tidw
        if self.has_index:
            self.store.indexes = part_out["index"]

        # operation replication: publish the epoch's ordered stream as one
        # slab — the replica-replay subscriber applies it, and any other
        # subscriber (materialized views, ...) rides the same publish
        self.changelog.publish_slab(part_out["log"], self.epoch)

        # ---- replication byte accounting, partitioned stream (Fig. 15) --
        # (host-side np on the write mask: the device is already idle here —
        # t_part was measured with block_until_ready above — and fence 1
        # needs the stream bytes to model its network drain; skipped
        # entirely when the batch carries no byte tables)
        vb = 0
        attr = self.changelog.attribute(batch, part_out["log"],
                                        self.has_index,
                                        lambda a: self._pad_axis(a, 1))
        vb_alt, slab_bytes, ib = attr.value_bytes_alt, attr.slab_bytes, \
            attr.index_op_bytes
        ob = attr.total                          # incl. index op bytes now

        # ---- fence 1: all streams applied, snapshot commit --------------
        # §5 overlap: the first n_slabs-1 stream slabs shipped DURING the
        # phase (their transfer hides under t_part); the fence waits only
        # on the unshipped tail slab
        t0 = time.perf_counter()
        ob_head, ob_tail = attr.overlapped, attr.fence
        if self.hybrid:
            t_net1 = self._fence(ob_tail, overlapped_bytes=ob_head,
                                 t_exec_s=t_part)
        else:
            t_net1 = self._fence(vb_alt)
        t_fence1 = time.perf_counter()
        t_f1 = t_fence1 - t0
        tr.complete("engine.fence", "fence", t0, t_fence1, which=1,
                    epoch=self.epoch, tail_bytes=ob_tail if self.hybrid
                    else vb_alt, overlapped_bytes=ob_head)

        # ---- single-master phase (cross-partition txns, Silo OCC) ------
        t0 = time.perf_counter()
        flat_val = self.store.val.reshape(self.P * self.R, self.C)
        flat_tid = self.store.tid.reshape(self.P * self.R)
        B = int(cross["row"].shape[0])
        if B > 0:
            fval, ftid, sm_out, sstats = self._jit_sm(
                flat_val, flat_tid, cross, epoch_u + jnp.uint32(0),
                max_rounds=self.max_rounds,
                index=self.store.indexes if self.has_index else None,
                kernel=self.kernel)
            jax.block_until_ready(fval)
            self.store.val = fval.reshape(self.P, self.R, self.C)
            self.store.tid = ftid.reshape(self.P, self.R)
            if self.has_index:
                self.store.indexes = sm_out["index"]
            # value replication, Thomas write rule (order-free) + the
            # round-ordered index-maintenance stream — published once,
            # applied by every subscriber
            self.changelog.publish_master(
                sm_out["log"],
                kinds=cross["kind"] if self.has_index else None,
                delta=cross["delta"] if self.has_index else None)
        else:
            sstats = {"committed": jnp.int32(0), "retries": jnp.int32(0),
                      "user_aborts": jnp.int32(0), "starved": jnp.int32(0),
                      "writes": jnp.int32(0)}
        t_sm = time.perf_counter() - t0
        # per-round kernel time: the single-master phase is max_rounds
        # identical fused-round launches (one per OCC round)
        t_sm_round = t_sm / self.max_rounds if B > 0 else 0.0
        tr.complete("engine.single_master", "phase", t0, t0 + t_sm,
                    epoch=self.epoch, rounds=self.max_rounds if B else 0)
        if tr.enabled and B > 0:
            # the rounds execute inside ONE jitted call; attribute the
            # measured phase time evenly (the same t_sm_round fig11 reports)
            for r in range(self.max_rounds):
                tr.complete("engine.sm_round", "phase",
                            t0 + r * t_sm_round, t0 + (r + 1) * t_sm_round,
                            epoch=self.epoch, round=r)

        # ---- byte accounting, single-master value stream ----------------
        ib_sm = 0
        if B > 0:
            cw = np.asarray(sm_out["log"]["write"])            # (rounds,B,M)
            if "c_row_bytes" in batch:
                crb = np.broadcast_to(self._pad_axis(batch["c_row_bytes"], 0),
                                      cw.shape[1:])
                vb = int(repl.value_bytes(cw, crb[None]))
            elif batch.get("row_bytes") is not None:
                vb = int(repl.value_bytes(cw, batch["row_bytes"][None, None, :]))
            if self.has_index and (vb or ob):
                # index ops ride the SM stream too — previously uncounted
                # in the fence's modeled bytes (fence-latency attribution)
                ib_sm = repl.index_op_bytes(sm_out["log"]["iwrite"])

        # ---- fence 2: epoch boundary ------------------------------------
        t0 = time.perf_counter()
        t_net2 = self._fence(vb + ib_sm, commit_epoch=self.epoch)
        self.epoch += 1
        t_fence2 = time.perf_counter()
        t_f2 = t_fence2 - t0
        tr.complete("engine.fence", "fence", t0, t_fence2, which=2,
                    epoch=self.epoch - 1, commit=True,
                    value_bytes=vb + ib_sm)

        # ---- controller telemetry ---------------------------------------
        nc = int(sstats["committed"])
        ns = int(pstats["committed"])
        self.controller.observe("partitioned", ns, t_part)
        self.controller.observe("single", nc, t_sm,
                                frac_cross=nc / max(nc + ns, 1))
        tau_p, tau_s = self.controller.plan()

        s = self.stats
        s.epochs += 1
        s.committed_single += ns
        s.committed_cross += nc
        s.user_aborts += int(pstats["user_aborts"]) + int(sstats["user_aborts"])
        s.consume_skips += int(pstats.get("consume_skips", 0)) \
            + int(sstats.get("consume_skips", 0))
        overflow = int(pstats.get("index_overflow", 0)) \
            + int(sstats.get("index_overflow", 0))
        s.index_overflow += overflow
        if self.strict_index and overflow:
            raise RuntimeError(
                f"ordered-index segment overflow: {overflow} live keys "
                f"dropped this epoch (IndexSpec capacity too small)")
        s.retries += int(sstats["retries"])
        s.part_time_s += t_part
        s.sm_time_s += t_sm
        s.sm_rounds += self.max_rounds if B > 0 else 0
        s.fence_time_s += t_f1 + t_f2
        s.value_bytes += vb
        s.op_bytes_hybrid += ob if self.hybrid else vb_alt
        s.value_bytes_if_not_hybrid += vb_alt
        s.index_op_bytes += ib + ib_sm
        if self.hybrid:
            s.op_bytes_overlapped += ob_head
            s.op_bytes_fence += ob_tail
            s.slabs_shipped += len(slab_bytes)
        # per-txn commit outcomes + fence stamps — the service layer maps
        # these back to queued requests (group commit at the epoch fence)
        p_committed = np.asarray(part_out["committed"])          # (P, T_pad)
        c_committed = (np.asarray(sm_out["committed"]) if B > 0
                       else np.zeros(B, bool))                   # (B_pad,)
        m = {"committed_single": ns, "committed_cross": nc,
             "tau_p_ms": tau_p, "tau_s_ms": tau_s,
             "t_part_s": t_part, "t_sm_s": t_sm,
             "t_sm_round_s": t_sm_round,
             "t_ingest_s": t_ingest,
             "t_fence1_s": t_fence1, "t_fence2_s": t_fence2,
             "t_fence_net_s": t_net1 + t_net2,
             "op_bytes_overlapped": ob_head if self.hybrid else 0,
             "op_bytes_fence": ob_tail if self.hybrid else vb_alt,
             "p_committed": p_committed, "c_committed": c_committed,
             "index_overflow": overflow,
             "starved": int(sstats["starved"])}
        if self.has_index:
            # which consume ops were skipped on EXPECT mismatch — the host
            # mirror (tpcc.apply_consume_feedback) re-queues these districts
            m["p_cskip"] = np.asarray(part_out["log"]["cskip"])  # (P,T,K)
            m["c_cskip"] = (np.asarray(sm_out["log"]["cskip"]).any(0)
                            if B > 0 else None)                  # (B_pad,K)
        tr.complete("engine.epoch", "epoch", t_ep0, time.perf_counter(),
                    epoch=self.epoch - 1, committed=ns + nc)
        return m

    # ------------------------------------------------------------------
    def _fence(self, stream_bytes: int = 0, commit_epoch=None,
               overlapped_bytes: int = 0, t_exec_s: float = 0.0) -> float:
        """Replication fence: all outstanding writes applied, then the commit
        point. In-process the streams are applied synchronously above, so the
        fence is the snapshot promotion + epoch bookkeeping; the inter-node
        cost is modeled through the Network envelope and returned (reported
        as ``t_fence_net_s``), not slept.

        ``stream_bytes`` drain entirely inside the fence (the unshipped
        tail); ``overlapped_bytes`` were shipped DURING the preceding
        ``t_exec_s`` of execution (§5 op-stream overlap) and surface at the
        fence only as the residue their transfer did not hide.

        ``commit_epoch`` (fence 2 only) retires the epoch through the
        changelog: the WAL sink appends the committed streams and fsyncs
        every worker's log inside the fence — the disk group commit — and
        the materialized views stamp the fence's aggregate snapshot."""
        self.store.snapshot_commit()
        self.replica_store.snapshot_commit()
        self.stats.fences += 1
        if commit_epoch is not None:
            self.committed_epoch = int(commit_epoch)
            _shipped, dropped = self.changelog.commit(commit_epoch)
            self.stats.ledger_dropped += dropped
        t_net = repl.fence_net_seconds(self.net, stream_bytes,
                                       overlapped_bytes, t_exec_s)
        self.stats.fence_net_s += t_net
        return t_net

    def committed_state(self):
        """The committed full-replica arrays — what a new changelog
        subscriber seeds its projection from."""
        sn = self.store.snapshot
        return sn["val"], sn["tid"]

    def replica_consistent(self) -> bool:
        return self.store.equals(self.replica_store)

    def read_views(self):
        """Committed snapshot views for the read tier's SnapshotCatalog:
        the master copy plus the (single-host) operation replica, both
        covering every partition with the identity row mapping — two
        independently load-balanceable serving copies.  Views reference
        the COMMITTED two-version snapshot, never the working arrays."""
        wm = self.changelog.watermark(self.committed_epoch)
        P = self.P
        cover = np.ones(P, bool)
        rop = np.arange(P, dtype=np.int64)
        views = []
        for rid, kind, store in (("full", "full", self.store),
                                 ("replica", "secondary",
                                  self.replica_store)):
            sn = store.snapshot
            views.append({"id": rid, "kind": kind, "node": 0,
                          "epoch": self.committed_epoch, "watermark": wm,
                          "cover": cover, "row_of_partition": rop,
                          "val": sn["val"], "tid": sn["tid"],
                          "idx": sn["indexes"] if self.has_index else []})
        return views

    # ------------------------------------------------------------------
    # fault tolerance (§4.5)
    # ------------------------------------------------------------------
    def inject_failure(self, failed: set[int], dirty: bool = True):
        """Simulate node failures mid-epoch: optionally scribble uncommitted
        writes into the working version, then run detection + revert."""
        if dirty:
            self.store.val = self.store.val.at[:, 0, 0].add(12345)
            self.store.tid = self.store.tid.at[:, 0].add(jnp.uint32(2))
        plan = make_recovery_plan(self.cluster, failed, self.epoch - 1)
        # revert to last committed epoch (two-version records, §4.5.2 —
        # indexes roll back with the records they point at); in-flight
        # stream slabs are discarded by every subscriber
        self.store.revert_to_snapshot()
        self.replica_store.load_state(self.store.snapshot)
        self.stats.slabs_discarded += self.changelog.revert(self.epoch)
        return plan

    def recover_node(self, plan):
        """Case-1 recovery: copy + Thomas-rule catch-up (here: resync from the
        committed snapshot, which the donor streams guarantee)."""
        self.replica_store.load_state(self.store.snapshot)
        return True
