"""The benchmark's clients: arrivals from a traffic file, content from the
configuration's transaction source.

Two loops, as a traffic file names them:

* ``"loop": "open"`` — requests arrive at Poisson times at ``rate_txn_s``,
  whether or not the service keeps up.  Latency runs from the scheduled
  arrival.
* ``"loop": "closed"`` — ``outstanding`` requests are in flight at all
  times; an answer issues the next one at once.  Latency runs from the
  issue time.

Requests are stamped on the service clock (seconds since the served run
started); the ledger keeps each request's stamp, so answers can be timed
on the host clock from the moment each request was due, and the content
of each request as it was issued, so the check can hold every batch slot
to the request it answers.
"""
from __future__ import annotations

import time

import numpy as np

GEN_CHUNK = 256
ISSUED = ("parts", "rows", "kinds", "deltas", "user_abort")
TRAFFIC_KEYS = {"loop", "rate_txn_s", "outstanding", "warmup_epochs",
                "setup_epochs", "trace_epochs", "slots_per_partition",
                "master_lanes", "admission", "kill"}


class Drained(Exception):
    """Arrivals have stopped and every request has an answer."""


class Ledger:
    """Issue stamps, answers and sheds of one run, for all its clients."""

    def __init__(self):
        self.origin = None            # host clock at service clock 0
        self.clients = {}             # tenant -> client
        self.stopped = False
        self.outstanding = 0
        self.answers = []             # (epoch, latencies_s, ok) per epoch
        self.shed_at = []             # host clock of every shed request
        self.epoch = -1               # fence index the proxy last recorded
        self.t_commit = 0.0           # host clock of that fence
        self.formed = {}              # fence index -> slot keys, below

    def anchor(self, service_now: float):
        if self.origin is None:
            self.origin = time.perf_counter() - service_now

    def check_drained(self):
        if self.stopped and self.outstanding == 0:
            raise Drained

    def issued(self, n: int):
        self.outstanding += n

    def shed(self, n: int, service_now: float):
        self.outstanding -= n
        self.shed_at.extend([self.origin + service_now] * n)

    def answer(self, tenants, txn_ids, ok):
        """Requests answered at the fence the proxy last recorded."""
        due = np.array([self.clients[int(t)].due_s[int(i)]
                        for t, i in zip(tenants, txn_ids)], np.float64)
        lat = self.t_commit - (self.origin + due)
        self.answers.append((self.epoch, lat, np.asarray(ok, bool)))
        self.outstanding -= len(due)

    def form(self, p_tenant, p_txn, c_tenant, c_txn):
        """The requests in the slots of the batch the proxy last recorded:
        ``(P, T)`` and ``(n_cross,)`` tenants and txn ids, -1 where empty."""
        self.formed[self.epoch] = (p_tenant, p_txn, c_tenant, c_txn)

    def requests(self):
        """Every issued request's content, ``{field: array}`` indexed by
        ``offset[tenant] + txn_id``, and those offsets."""
        offset, parts, n = {}, [], 0
        for tenant, c in sorted(self.clients.items()):
            offset[tenant] = n
            n += len(c.due_s)
            parts.extend(c.issued)
        out = {k: np.concatenate([q[k] for q in parts]) for k in ISSUED} \
            if parts else None
        return out, offset


class _Client:
    def __init__(self, source, ledger: Ledger, tenant: int):
        self.source = source
        self.ledger = ledger
        self.tenant = tenant
        self.due_s = []               # service-clock stamp per txn_id
        self.issued = []              # request content per issued chunk
        ledger.clients[tenant] = self

    def _stamp(self, req, due):
        n = len(due)
        first = len(self.due_s)
        req["arrival_s"] = np.asarray(due, np.float64)
        req["tenant"] = np.full(n, self.tenant, np.int32)
        req["txn_id"] = np.arange(first, first + n, dtype=np.int64)
        self.due_s.extend(float(t) for t in due)
        self.issued.append({k: np.array(req[k]) for k in ISSUED})
        return req

    def _unclaim(self, req):
        unclaim = getattr(self.source, "unclaim", None)
        if unclaim is not None:
            unclaim(req)

    def push_back(self, req):
        raise RuntimeError("the benchmark runs admission with shedding")


class OpenLoop(_Client):
    def __init__(self, source, ledger, rate_txn_s: float, seed: int,
                 tenant: int = 0):
        super().__init__(source, ledger, tenant)
        self.rate = float(rate_txn_s)
        self.rng = np.random.default_rng(seed)
        self._t = 0.0
        self._pending = None          # generated, not yet due

    def _gaps(self, n):
        return self.rng.exponential(1.0 / self.rate, n)

    def _chunk(self):
        arrivals = self._t + np.cumsum(self._gaps(GEN_CHUNK))
        self._t = float(arrivals[-1])
        req = self.source.generate(GEN_CHUNK)
        req["arrival_s"] = arrivals
        return req

    def pull(self, until_s: float):
        self.ledger.anchor(until_s)
        if self.ledger.stopped:
            self.ledger.check_drained()
            return None
        chunks = []
        while True:
            if self._pending is not None:
                due = self._pending["arrival_s"] <= until_s
                if due.any():
                    chunks.append({k: v[due] for k, v in
                                   self._pending.items()})
                    rest = ~due
                    self._pending = ({k: v[rest] for k, v in
                                      self._pending.items()}
                                     if rest.any() else None)
                if self._pending is not None:
                    break
            if self._t > until_s:
                break
            self._pending = self._chunk()
        if not chunks:
            return None
        req = {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}
        req = self._stamp(req, req["arrival_s"])
        self.ledger.issued(len(req["arrival_s"]))
        return req

    def on_shed(self, req, now_s: float):
        self.ledger.shed(len(req["arrival_s"]), now_s)
        self._unclaim(req)


class ClosedLoop(_Client):
    def __init__(self, source, ledger, outstanding: int, tenant: int = 0):
        super().__init__(source, ledger, tenant)
        self._due = [0.0] * int(outstanding)

    def pull(self, until_s: float):
        self.ledger.anchor(until_s)
        if self.ledger.stopped:
            self.ledger.check_drained()
            return None
        due = sorted(t for t in self._due if t <= until_s)
        if not due:
            return None
        self._due = [t for t in self._due if t > until_s]
        req = self._stamp(self.source.generate(len(due)), due)
        self.ledger.issued(len(due))
        return req

    def on_complete(self, n: int, now_s: float):
        self._due.extend([float(now_s)] * n)

    def on_shed(self, req, now_s: float):
        n = len(req["arrival_s"])
        self.ledger.shed(n, now_s)
        self._unclaim(req)
        self._due.extend([now_s] * n)


def make_client(traffic: dict, source, ledger: Ledger, seed: int):
    """The client a traffic file describes; a key it does not know is an
    error, so that no file asks for arrivals this generator cannot make."""
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    loop = traffic["loop"]
    if loop == "open":
        return OpenLoop(source, ledger, traffic["rate_txn_s"], seed)
    if loop == "closed":
        return ClosedLoop(source, ledger, traffic["outstanding"])
    raise ValueError(f"unknown loop {loop!r}")
