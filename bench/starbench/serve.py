"""Drive one cell through the served path and record what it did.

The cell runs ``TxnService.run`` over ``StarEngine`` on one chip, or
``ClusterTxnService.run`` over ``ClusterRuntime`` on several.  Between the
service
and the engine sits a proxy that records every batch the engine is
handed,
the commit decisions it returns, and the host clock at each commit fence.
The proxy also closes the window: after ``setup_epochs`` loaded epochs the
window opens at a fence; at the first fence ``seconds`` later it closes,
arrivals stop, and the service drains what it admitted.
"""
from __future__ import annotations

import contextlib
import resource
import time

import numpy as np

from starbench import stats
from starbench import world as worldmod
from starbench.traffic import Drained, Ledger, make_client

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
DRAIN_EPOCHS = 12           # a drain longer than this leaves requests unanswered
# the engine's host-clock split of an epoch: phase times and fence clocks
SPLIT = ("t_ingest_s", "t_part_s", "t_sm_s", "t_fence1_s", "t_fence2_s")


class CompileCounter:
    """Counts traces and backend compiles that JAX reports."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.n += 1


def process_cpu_s() -> float:
    """CPU seconds this process has used, all threads: over an epoch, it
    tells host work from waiting."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


class EngineProxy:
    """Stands in for the engine: forwards everything, records each epoch."""

    def __init__(self, engine, run):
        self._eng = engine
        self._run = run

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def run_epoch(self, batch, ingest=None):
        run = self._run
        run.before_epoch()
        fault = run.fault
        run_batch = batch if fault is None else fault.before(run, batch)
        hook = ingest
        if ingest is not None and run.annotate:
            def hook():
                with run.annotate("service.ingest"):
                    ingest()
        cpu_call = process_cpu_s()
        t_call = time.perf_counter()
        with run.annotate_or_null("engine.run_epoch"):
            m = self._eng.run_epoch(run_batch, ingest=hook)
        t_fence = time.perf_counter()
        cpu = process_cpu_s() - cpu_call
        if fault is not None:
            fault.after(run, batch, m)
        run.after_epoch(batch, m, t_call, t_fence, cpu)
        return m


def served(base):
    """``base`` (a TxnService class) that reports to the run's ledger which
    request each slot of the batch held, and each answered request, before
    it retires them."""

    class Served(base):
        ledger: Ledger = None

        def _complete(self, plan, metrics):
            pool = self.admission.pool
            p_live = plan.p_idx >= 0
            p_slot = np.where(p_live, plan.p_idx, 0)
            self.ledger.form(np.where(p_live, pool.tenant[p_slot], -1),
                             np.where(p_live, pool.txn_id[p_slot], -1),
                             pool.tenant[plan.c_idx].copy(),
                             pool.txn_id[plan.c_idx].copy())
            flat = plan.p_idx.reshape(-1)
            live = flat >= 0
            T = plan.p_idx.shape[1]
            p_ok = np.asarray(metrics["p_committed"])[:, :T].reshape(-1)[live]
            c = plan.c_idx
            c_ok = np.asarray(metrics["c_committed"])[:c.size]
            done = c_ok | pool.user_abort[c]          # the rest retry
            slots = np.concatenate([flat[live], c[done]])
            ok = np.concatenate([p_ok, c_ok[done]])
            self.ledger.answer(pool.tenant[slots], pool.txn_id[slots], ok)
            super()._complete(plan, metrics)

    return Served


class Run:
    """One served run of a cell: the records the metrics and the check
    read.

    epochs: per engine call, a dict with the batch, the commit masks, the
    engine epoch number, the host clock of the call and of the fence, the
    engine's own host-clock split of the call, the process's CPU seconds
    over the call, and whether it was a warm-up epoch."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 trace_dir=None, fault=None, devices=None):
        self.spec = spec
        self.devices = devices
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.trace_dir = trace_dir
        self.fault = fault
        self.traffic = spec["traffic"]
        self.epochs = []
        self.phase = "build"
        self.window = None            # (start fence index, end fence index)
        self.trace_span = None        # (first, last) fence index traced
        self.ledger = Ledger()
        self.compiles = CompileCounter()
        self.compiles_at = {}
        self.t_window_open = None
        self.recovery = None          # (fence index, gap seconds, event)
        self.annotate = None
        if trace:
            import jax
            self.annotate = jax.profiler.TraceAnnotation

    def annotate_or_null(self, name):
        return self.annotate(name) if self.annotate else \
            contextlib.nullcontext()

    # -- proxy hooks ------------------------------------------------------
    def before_epoch(self):
        if self.window is not None and self.window[1] is not None and \
                len(self.epochs) - self.window[1] > DRAIN_EPOCHS:
            raise Drained       # the drain did not finish: give up on it

    def after_epoch(self, batch, m, t_call, t_fence, cpu_s):
        rec = {"batch": batch,
               "p_committed": np.asarray(m["p_committed"]),
               "c_committed": np.asarray(m["c_committed"]),
               "engine_epoch": self.engine.epoch - 1,
               "t_call": t_call, "t_fence": t_fence,
               "warmup": self.phase == "warmup",
               "n_txn": batch["n_single"] + batch["n_cross"],
               "split": {k: m.get(k) for k in SPLIT}, "cpu_s": cpu_s}
        self.epochs.append(rec)
        k = len(self.epochs) - 1
        if "recovery" in m:
            self.recovery = (k, t_fence - self.epochs[k - 1]["t_fence"],
                             m["recovery"])
        self.ledger.epoch, self.ledger.t_commit = k, t_fence
        if self.phase != "serve":
            return
        if self.window is None:
            loaded = sum(1 for e in self.epochs
                         if not e["warmup"] and e["n_txn"])
            if loaded >= self.traffic["setup_epochs"]:
                self.window = (k, None)
                self.t_window_open = t_fence
                self.compiles_at["open"] = self.compiles.n
                kill = self.traffic.get("kill")
                if kill:
                    self.engine.injector.schedule_kill(
                        node=kill["node"],
                        epoch=self.engine.epoch + kill["window_epoch"] - 1)
            return
        start, end = self.window
        if end is not None:
            return
        elapsed = t_fence - self.epochs[start]["t_fence"]
        if self.trace and self.trace_span is None:
            # profile the window's last epochs, so that writing the trace
            # falls after the window, when arrivals have stopped
            per_epoch = elapsed / (k - start)
            if elapsed + (self.traffic["trace_epochs"] + 1) * per_epoch \
                    >= self.seconds:
                import jax
                jax.profiler.start_trace(str(self.trace_dir))
                self.trace_span = (k, None)
                return
        fences = [e["t_fence"] for e in self.epochs]
        if stats.window_end(fences, start, self.seconds) is not None and (
                not self.trace or self.trace_span is not None):
            self.window = (start, k)
            self.compiles_at["close"] = self.compiles.n
            self.depth_at_close = self.service.admission.depth()
            self.ledger.stopped = True
            if self.trace_span is not None:
                import jax
                jax.profiler.stop_trace()
                self.trace_span = (self.trace_span[0], k)

    # -- the run ------------------------------------------------------------
    def _engine(self, w):
        """StarEngine on one chip; on more, one STAR node per chip over a
        ``("part",)`` mesh (full replica on the first, physical
        secondaries), with the traffic's node kill scheduled later."""
        cfg = self.spec["config"]
        P, R, C = w.init_val.shape
        chips = self.spec["cell"]["chips"]
        if chips == 1:
            from repro.core.engine import StarEngine
            from repro.service import TxnService
            return StarEngine(P, R, C, init_val=w.init_val,
                              indexes=w.index_specs, kernel="jnp",
                              max_rounds=cfg["occ_rounds"]), TxnService
        import jax
        from jax.sharding import Mesh
        from repro.cluster import ClusterRuntime, ClusterTxnService
        from repro.core.fault import FaultInjector
        devs = self.devices or jax.devices()[:chips]
        mesh = Mesh(np.array(devs), ("part",))
        return ClusterRuntime(mesh, P, R, C, init_val=w.init_val,
                              indexes=w.index_specs,
                              max_rounds=cfg["occ_rounds"],
                              injector=FaultInjector()), ClusterTxnService

    def serve(self):
        """Build, warm up, serve the window and drain."""
        from repro.service import AdmissionConfig
        arrival_seed = worldmod.seeds(self.seed, 3)[2]
        self.world = w = worldmod.build(self.spec["config"], self.seed)
        self.engine, service_cls = self._engine(w)
        client = make_client(self.traffic, w.source, self.ledger,
                             arrival_seed)
        tr = self.traffic
        svc = served(service_cls)(
            EngineProxy(self.engine, self),
            [client], admission_cfg=AdmissionConfig(**tr["admission"]),
            slots_per_partition=tr["slots_per_partition"],
            master_lanes=tr["master_lanes"], feedback=w.feedback)
        svc.ledger = self.ledger
        self.service = svc
        self.phase = "warmup"
        svc.warmup(tr["warmup_epochs"])
        self.phase = "serve"
        kill = tr.get("kill")
        if kill:
            # a first kill in set-up compiles the recovery programs, so
            # the one in the window runs from compiled code
            self.engine.injector.schedule_kill(node=kill["node"],
                                               epoch=self.engine.epoch)
        try:
            svc.run(duration_s=float("inf"), warmup_epochs=0)
        except Drained:
            pass
        if self.window is None or self.window[1] is None:
            raise RuntimeError("the run ended before its window closed")
        self.t_end = time.perf_counter()

    def release(self):
        """Drop the program's state (the reference runs after)."""
        self.service = None
        self.engine = None
