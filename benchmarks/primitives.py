"""Paper Figure 11 + Table 2 (Appendix D): communication primitives.

Three parts:
  1. *Measured* (host devices, wall-clock): ODC p2p primitives
     (ppermute ring gather / scatter-accumulate) vs fused collectives
     (all_gather / psum_scatter) — same result, same total volume.
  2. *Analytic* (Table 2): per-client intra/inter-node volumes for
     collective (hierarchical ring) vs ODC p2p, showing ODC's extra
     inter-node traffic — the Fig. 11 inter-node gap.
  3. *Measured* (schedule='overlap' issue orders): a stacked L-layer shard
     set gathered as one fused chain vs L independently-issued per-layer
     chains (the prefetch issue order — each chain depends only on its own
     layer's shard, so the scheduler may interleave them with compute).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import odc


def _time(fn, *args, iters=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run_measured(sizes=(1 << 16, 1 << 20, 1 << 22)):
    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()), ("x",))
    rows = []
    for sz in sizes:
        x = jnp.arange(sz, dtype=jnp.float32)
        per = sz // n

        def g_coll(v):
            return jax.lax.all_gather(v, "x", tiled=True)

        def g_odc(v):
            return odc.ring_gather(v, "x")

        def s_coll(v):
            return jax.lax.psum_scatter(v, "x", scatter_dimension=0,
                                        tiled=True)

        def s_odc(v):
            return odc.ring_scatter_accumulate(v, "x")

        for name, inner, spec_in, spec_out in [
            ("all_gather", g_coll, P("x"), P(None)),
            ("odc_gather", g_odc, P("x"), P(None)),
            ("reduce_scatter", s_coll, P(None), P("x")),
            ("odc_scatter_accumulate", s_odc, P(None), P("x")),
        ]:
            f = jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=spec_in,
                                      out_specs=spec_out, check_vma=False))
            dt = _time(f, x)
            moved = 4 * per * (n - 1) * n  # bytes on the wire, total
            rows.append({
                "primitive": name, "bytes": 4 * sz,
                "us_per_call": dt * 1e6,
                "algo_bw_GBs": moved / dt / 1e9,
            })
    return rows


def table2(D=32, G=8, K=1.0):
    """Per-client communication volume (units of K)."""
    rows = []
    for prim in ("gather", "scatter_accumulate"):
        rows.append({
            "primitive": f"collective_{prim}", "D": D, "G": G,
            "intra_node": (G - 1) / G * (D - 1) * K,
            "inter_node": (D - 1) / G * K,
            "total": (D - 1) * K,
        })
        rows.append({
            "primitive": f"odc_{prim}", "D": D, "G": G,
            "intra_node": (G - 1) * K,
            "inter_node": (D - G) * K,
            "total": (D - 1) * K,
        })
    return rows


def run_overlap_issue(layers=4, per_layer=1 << 18):
    """schedule='overlap' issue orders, measured at the primitive level:

      fused      one gather over the whole L-layer stack (the 'minibatch'
                 schedule's monolithic materialization — downstream compute
                 waits for ALL layers)
      pipelined  L per-layer gathers, each depending only on its own
                 layer's shard (the prefetch issue order — layer l's
                 consumer can start while layer l+1's chain is in flight)

    Total bytes moved are identical; what differs is the dependence
    structure the scheduler sees (and, on hardware, the exposed latency —
    repro.sim charges that; here we check parity and report wall-clock).
    """
    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()), ("x",))
    x = jnp.arange(layers * per_layer, dtype=jnp.float32)
    x = x.reshape(layers, per_layer)

    def fused(v):  # (L, c) -> one chain over the flattened stack
        c = v.shape[1]
        flat = odc.ring_gather(v.reshape(-1), "x")  # device-major concat
        return flat.reshape(-1, layers, c).swapaxes(0, 1).reshape(layers, -1)

    def pipelined(v):  # L independent per-layer chains
        return jnp.stack([odc.ring_gather(v[l], "x")
                          for l in range(layers)])

    rows = []
    outs = {}
    for name, inner in [("odc_gather_fused_Llayers", fused),
                        ("odc_gather_pipelined_Llayers", pipelined)]:
        f = jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=P(None, "x"), out_specs=P(None),
            check_vma=False))
        dt = _time(f, x)
        outs[name] = np.asarray(f(x))
        moved = 4 * layers * (per_layer // n) * (n - 1) * n
        rows.append({
            "primitive": name, "bytes": 4 * layers * per_layer,
            "us_per_call": dt * 1e6,
            "algo_bw_GBs": moved / dt / 1e9,
        })
    assert np.array_equal(*outs.values()), "issue orders must agree"
    return rows


def run():
    rows = run_measured()
    rows += run_overlap_issue()
    for r in table2():
        r["us_per_call"] = ""
        rows.append(r)
    return rows


def validate(rows):
    msgs = []
    meas = [r for r in rows if "algo_bw_GBs" in r and r.get("algo_bw_GBs")]
    # intra-host: ODC within 10x of collective (CPU wall-times are noisy;
    # the paper's claim is parity intra-node, big gap only inter-node).
    # meas is empty on a single-device run (no XLA_FLAGS device count) —
    # there is no ring to measure, skip the wall-clock checks.
    if meas:
        biggest = max(r["bytes"] for r in meas)
        ag = next(r for r in meas if r["primitive"] == "all_gather"
                  and r["bytes"] == biggest)
        og = next(r for r in meas if r["primitive"] == "odc_gather"
                  and r["bytes"] == biggest)
        if og["us_per_call"] > 30 * ag["us_per_call"]:
            msgs.append("odc gather wildly slower than collective intra-host")
    # Table 2: totals identical
    t2 = [r for r in rows if "total" in r]
    for prim in ("gather", "scatter_accumulate"):
        c = next(r for r in t2 if r["primitive"] == f"collective_{prim}")
        o = next(r for r in t2 if r["primitive"] == f"odc_{prim}")
        if abs(c["total"] - o["total"]) > 1e-9:
            msgs.append(f"Table2 totals differ for {prim}")
        if o["inter_node"] <= c["inter_node"]:
            msgs.append(f"Table2: ODC inter-node not larger for {prim}")
    return msgs


def main():
    from benchmarks.common import emit
    rows = run()
    emit(rows, header=["primitive", "bytes", "us_per_call", "algo_bw_GBs",
                       "D", "G", "intra_node", "inter_node", "total"])
    msgs = validate(rows)
    print("# validation:", "OK" if not msgs else "; ".join(msgs))
    return 0 if not msgs else 1


if __name__ == "__main__":
    raise SystemExit(main())
