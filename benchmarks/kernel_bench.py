"""Tracked per-kernel perf harness for the Pallas OCC/index kernels.

For each kernel — ``occ_round`` (the 3-launch lock/validate/install
pipeline), ``scan_window`` (the scalar-prefetch index probe) and
``index_merge`` (the fused delete-compact + rank + scatter merge) — at
TPC-C shapes P ∈ {4, 16} × index cap ∈ {11520, 65536}, this emits:

* measured wall time per call (interpret mode on this host — no TPU in
  the container; on hardware the same rows track the lowered kernels),
* modeled HBM bytes per call for each dispatch generation
  (``occ_round_bytes`` / ``index_merge_bytes`` — the jnp reference's
  whole-segment gathers vs the fused kernels' resident-segment streams),
* the roofline fraction: modeled-bytes/HBM_BW ideal time over measured
  wall time (≪1 in interpret mode by construction; meaningful on TPU).

``--bench-json BENCH_kernels.json`` writes the schema-versioned snapshot
(the committed tracking artifact, like BENCH_fig11.json).  ``--smoke``
runs tiny shapes + bit-equality parity and gates the modeled traffic
claim (fused merge ≥ 2x less HBM traffic than the jnp gather merge at
TPC-C scale) for CI; ``--validate`` runs the parity checks only.

    PYTHONPATH=src python -m benchmarks.kernel_bench --smoke
    PYTHONPATH=src python -m benchmarks.kernel_bench --bench-json BENCH_kernels.json
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from benchmarks.common import timed

SCHEMA = 1
SHAPES = [(4, 11520), (16, 11520), (4, 65536), (16, 65536)]
B, M, K, C = 128, 24, 12, 10           # single-master lane batch shape
Q_MERGE = 1536                         # per-partition merge ops per call


def _mb(b):
    return f"{b / 1e6:.1f}MB"


def _roofline_us(nbytes):
    from repro.launch.roofline import HBM_BW
    return nbytes / HBM_BW * 1e6


# ---------------------------------------------------------------------------
# workload builders (seeded, numpy-side)
# ---------------------------------------------------------------------------
def _merge_args(rng, P, cap, Q):
    from repro.storage.index import SENTINEL
    key = np.full((P, cap), SENTINEL, np.int32)
    n_live = cap // 2
    for p in range(P):
        key[p, :n_live] = np.sort(rng.choice(cap * 4, n_live, replace=False))
    live = key != SENTINEL
    prow = np.where(live, rng.integers(0, cap, (P, cap)), 0).astype(np.int32)
    tid = np.where(live, rng.integers(1, 99, (P, cap)), 0).astype(np.uint32)
    Kd = Ki = Q // 2
    del_pq = np.stack([rng.choice(key[p, :n_live], Kd) for p in range(P)])
    ins_pq = rng.integers(0, cap * 4, (P, Ki)).astype(np.int32)
    prow_pq = rng.integers(0, cap, (P, Ki)).astype(np.int32)
    tid_pq = rng.integers(1, 99, (P, Ki)).astype(np.uint32)
    return tuple(jnp.asarray(a) for a in
                 (key, prow, tid, del_pq.astype(np.int32), ins_pq,
                  prow_pq, tid_pq))


def _scan_args(rng, P, cap, Q, n_slots):
    S = P * cap
    fk = np.sort(rng.integers(0, cap * 4, (P, cap)).astype(np.int32),
                 axis=1).reshape(S)
    ft = rng.integers(0, 99, S).astype(np.uint32)
    q = rng.integers(0, cap * 4, Q).astype(np.int32)
    seg_base = (rng.integers(0, P, Q) * cap).astype(np.int32)
    seg_cap = np.full(Q, cap, np.int32)
    n_iters = int(cap).bit_length() + 1
    return tuple(jnp.asarray(a) for a in (fk, ft, q, seg_base, seg_cap)), \
        n_iters


def _scan_window_jnp(flat_key, flat_tid, q, seg_base, seg_cap, n_slots):
    """The reference probe's traffic shape: gather each query's WHOLE
    segment, searchsorted, then the window gather (cf.
    ref.locate_index_ops_ref) — what the fused kernel replaces."""
    import jax
    cap = int(seg_cap[0])
    seg = flat_key[seg_base[:, None] + jnp.arange(cap, dtype=jnp.int32)]
    pos = jax.vmap(jnp.searchsorted)(seg, q).astype(jnp.int32)
    window = pos[:, None] + jnp.arange(n_slots, dtype=jnp.int32)
    slots = jnp.clip(window, 0, seg_cap[:, None] - 1)
    gidx = seg_base[:, None] + slots
    return pos, flat_key[gidx], flat_tid[gidx]


def scan_window_bytes(P, cap, Q, n_slots):
    """Modeled HBM bytes per probe call (int32/uint32 words): the jnp
    reference gathers (Q, cap) keys; the kernel streams the resident
    segments once + O(log cap + n_slots) elements per query."""
    W = 4
    n_iters = int(cap).bit_length() + 1
    return {"jnp": W * (Q * cap + 3 * Q + 2 * Q * n_slots),
            "pallas": W * (2 * P * cap + Q * (n_iters + 3 + 2 * n_slots))}


def _occ_args(rng, P, cap, n_rows, b, m, k, c, scan_l):
    val = jnp.asarray(rng.integers(0, 100, (n_rows, c)), jnp.int32)
    tidw = jnp.asarray(rng.integers(0, 50, n_rows), jnp.uint32)
    rows = jnp.asarray(
        np.stack([rng.choice(n_rows, m, replace=False) for _ in range(b)]),
        jnp.int32)
    kind = jnp.asarray(rng.integers(0, 4, (b, m)), jnp.int32)
    delta = jnp.asarray(rng.integers(-3, 3, (b, m, c)), jnp.int32)
    wmask = jnp.asarray(rng.random((b, m)) < 0.5)
    amask = wmask | jnp.asarray(rng.random((b, m)) < 0.5)
    active = jnp.asarray(rng.random(b) < 0.9)
    last_tid = jnp.asarray(rng.integers(0, 50, b), jnp.uint32)
    NT = n_rows + P * cap
    ix = {"claim_addr": jnp.asarray(
              rng.integers(n_rows, NT, (b, k)), jnp.int32),
          "claim_tid": jnp.asarray(rng.integers(0, 50, (b, k)), jnp.uint32),
          "scan_addr": jnp.asarray(
              rng.integers(n_rows, NT + 1, (b, k, scan_l + 1)), jnp.int32),
          "scan_tid": jnp.asarray(
              rng.integers(0, 50, (b, k, scan_l + 1)), jnp.uint32),
          "scan_valid": jnp.asarray(rng.random((b, k, scan_l + 1)) < 0.5),
          "no_addr": NT}
    has_claim = jnp.asarray(rng.random((b, k)) < 0.5)
    return (val, tidw, rows, kind, delta, wmask, amask, active, last_tid,
            ix, has_claim, NT)


# ---------------------------------------------------------------------------
# per-kernel benches
# ---------------------------------------------------------------------------
def bench_index_merge(P, cap, Q, reps):
    from repro.kernels.index_merge.ops import index_merge, index_merge_bytes
    rng = np.random.default_rng(0)
    args = _merge_args(rng, P, cap, Q)
    bts = index_merge_bytes(P, cap, Q)
    lbl = f"kernels/index_merge/p{P}_cap{cap}"
    rows = [(f"{lbl}/argsort_modeled", 0.0, _mb(bts["argsort"]))]
    for kern in ("jnp", "pallas"):
        us, _ = timed(lambda k=kern: index_merge(*args, use_pallas=k ==
                                                 "pallas"), reps=reps)
        us *= 1e6
        frac = _roofline_us(bts[kern]) / max(us, 1e-9)
        rows += [(f"{lbl}/{kern}", us, _mb(bts[kern])),
                 (f"{lbl}/{kern}_roofline_frac", 0.0, round(frac, 5))]
    rows.append((f"{lbl}/traffic_x", 0.0,
                 round(bts["jnp"] / bts["pallas"], 1)))
    return rows


def bench_scan_window(P, cap, Q, n_slots, reps):
    from repro.kernels.occ.kernel import scan_window_pallas
    rng = np.random.default_rng(1)
    args, n_iters = _scan_args(rng, P, cap, Q, n_slots)
    bts = scan_window_bytes(P, cap, Q, n_slots)
    lbl = f"kernels/scan_window/p{P}_cap{cap}"
    rows = []
    runs = {"jnp": lambda: _scan_window_jnp(*args, n_slots),
            "pallas": lambda: scan_window_pallas(
                *args, n_slots=n_slots, n_iters=n_iters, interpret=True)}
    for kern, fn in runs.items():
        us, _ = timed(fn, reps=reps)
        us *= 1e6
        frac = _roofline_us(bts[kern]) / max(us, 1e-9)
        rows += [(f"{lbl}/{kern}", us, _mb(bts[kern])),
                 (f"{lbl}/{kern}_roofline_frac", 0.0, round(frac, 5))]
    rows.append((f"{lbl}/traffic_x", 0.0,
                 round(bts["jnp"] / bts["pallas"], 1)))
    return rows


def bench_occ_round(P, cap, n_rows, b, m, k, c, reps):
    from repro.kernels.occ.ops import occ_round, occ_round_bytes
    from repro.storage.index import SCAN_L
    rng = np.random.default_rng(2)
    (val, tidw, rows_a, kind, delta, wmask, amask, active, last_tid,
     ix, has_claim, NT) = _occ_args(rng, P, cap, n_rows, b, m, k, c, SCAN_L)
    bts = occ_round_bytes(B=b, M=m, K=k, C=c, n_rows=n_rows,
                          index_caps=[cap], n_indexes_P=P)
    lbl = f"kernels/occ_round/p{P}_cap{cap}"
    rows = []
    for kern in ("jnp", "pallas"):
        us, _ = timed(lambda kn=kern: occ_round(
            val, tidw, rows_a, kind, delta, wmask, amask, active,
            jnp.uint32(2), last_tid, ix, has_claim, kernel=kn), reps=reps)
        us *= 1e6
        frac = _roofline_us(bts[kern]) / max(us, 1e-9)
        rows += [(f"{lbl}/{kern}", us, _mb(bts[kern])),
                 (f"{lbl}/{kern}_roofline_frac", 0.0, round(frac, 5))]
    rows.append((f"{lbl}/traffic_x", 0.0,
                 round(bts["jnp"] / bts["pallas"], 1)))
    return rows


def model_rows():
    """The modeled-traffic claim rows at full TPC-C shapes — computed in
    every mode (smoke included): the ≥2x fused-merge claim gates on these."""
    from repro.kernels.index_merge.ops import index_merge_bytes
    rows = []
    for P, cap in SHAPES:
        bts = index_merge_bytes(P, cap, Q_MERGE)
        rows.append((f"kernels/index_merge/p{P}_cap{cap}/modeled_traffic_x",
                     0.0, round(bts["jnp"] / bts["pallas"], 1)))
    return rows


def validate():
    """Bit-equality parity at moderate shapes (all three kernels)."""
    import jax
    from repro.kernels.index_merge.ops import index_merge
    from repro.kernels.occ.kernel import scan_window_pallas
    from repro.kernels.occ.ops import occ_round
    from repro.storage.index import SCAN_L

    rng = np.random.default_rng(9)
    P, cap, Q = 3, 96, 24
    args = _merge_args(rng, P, cap, Q)
    a, b_ = index_merge(*args, use_pallas=False), \
        index_merge(*args, use_pallas=True)
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(a, b_)), \
        "index_merge parity"

    sargs, n_iters = _scan_args(rng, P, cap, Q, SCAN_L + 1)
    a = _scan_window_jnp(*sargs, SCAN_L + 1)
    b_ = scan_window_pallas(*sargs, n_slots=SCAN_L + 1, n_iters=n_iters,
                            interpret=True)
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(a, b_)), \
        "scan_window parity"

    (val, tidw, rows_a, kind, delta, wmask, amask, active, last_tid,
     ix, has_claim, NT) = _occ_args(rng, P, cap, 64, 16, 6, 4, 5, SCAN_L)
    outs = [occ_round(val, tidw, rows_a, kind, delta, wmask, amask, active,
                      jnp.uint32(2), last_tid, ix, has_claim, kernel=kn)
            for kn in ("jnp", "pallas")]
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(*outs)), \
        "occ_round parity"
    print("PARITY OK index_merge scan_window occ_round")


def run(smoke: bool = False):
    from repro.storage.index import SCAN_L
    rows = model_rows()
    if smoke:
        shapes, q, b, reps = [(2, 512)], 64, 8, 1
        m, k = 6, 4
    else:
        shapes, q, b, reps = SHAPES, Q_MERGE, B, 3
        m, k = M, K
    for P, cap in shapes:
        rows += bench_index_merge(P, cap, q, reps)
        rows += bench_scan_window(P, cap, q, SCAN_L + 1, reps)
        rows += bench_occ_round(P, cap, min(2880 * P, 4 * cap), b, m, k, C,
                                reps)
    return rows


def main():
    import argparse
    import json

    from benchmarks.common import emit
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + parity + traffic-claim gate (CI)")
    ap.add_argument("--validate", action="store_true",
                    help="bit-equality parity checks only")
    ap.add_argument("--bench-json", metavar="PATH", default=None,
                    help="write the snapshot, e.g. BENCH_kernels.json")
    args = ap.parse_args()
    if args.validate:
        validate()
        return
    if args.smoke:
        validate()
    rows = run(smoke=args.smoke)
    print("name,us_per_call,derived")
    emit(rows)
    # the tracked claim: fused merge moves ≥2x less modeled HBM traffic
    # than the jnp gather merge per vmapped call at TPC-C scale
    ratios = {r[0]: r[2] for r in rows if r[0].endswith("modeled_traffic_x")}
    assert ratios and all(v >= 2.0 for v in ratios.values()), \
        f"fused-merge traffic claim regressed: {ratios}"
    if args.bench_json:
        bench = {
            "schema": SCHEMA,
            "shapes": [list(s) for s in SHAPES],
            "smoke": bool(args.smoke),
            "merge_traffic_x": {k.split("/")[2]: v for k, v in
                                ratios.items()},
            "rows": {r[0]: r[2] for r in rows},
            "us_per_call": {r[0]: round(r[1], 3) for r in rows if r[1]},
        }
        with open(args.bench_json, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.bench_json}")
    if args.smoke:
        back = {r[0] for r in rows}
        assert any(n.startswith("kernels/index_merge/") for n in back)
        assert any(n.startswith("kernels/scan_window/") for n in back)
        assert any(n.startswith("kernels/occ_round/") for n in back)
        print("SMOKE OK " + " ".join(sorted(ratios)))


if __name__ == "__main__":
    main()
