"""CloverLeaf 2D at 3x the fast-memory capacity — the paper's headline
experiment, end to end through the Session API: lazy recording with inferred
stencils, dt-reduction chain breakers, skewed tiling, 3-slot streaming with
the Cyclic + Prefetch optimisations, memoised chain plans, and the
achieved-bandwidth metric vs. the resident baseline.

  PYTHONPATH=src python examples/cloverleaf_outofcore.py
"""
import numpy as np

from repro.apps import CloverLeaf2D
from repro.compile_cache import enable_compile_cache
from repro.core import P100_NVLINK, Session


def main():
    enable_compile_cache()
    capacity = 4 << 20               # scaled-down "16 GB"
    nx = 450                         # ~3x capacity with 25 fp32 datasets
    app_probe = CloverLeaf2D(nx, nx)
    ratio = app_probe.total_bytes() / capacity
    print(f"problem: {app_probe.total_bytes() / 1e6:.1f} MB "
          f"= {ratio:.1f}x fast memory ({capacity / 1e6:.0f} MB)")

    hw = P100_NVLINK.with_(fast_capacity=capacity, fast_bw=470e9, dd_bw=509.7e9)
    steps = 3

    ref_app = CloverLeaf2D(nx, nx, summary_every=steps)
    ref_summary = ref_app.run(Session("reference"), steps=steps)

    app = CloverLeaf2D(nx, nx, summary_every=steps)
    sess = Session("ooc", hw=hw, prefetch=True)
    summary = app.run(sess, steps=steps)   # enables cyclic after init

    err = np.abs(ref_app.d("density0").interior()
                 - app.d("density0").interior()).max()
    print(f"correctness vs in-core reference: max|drho| = {err:.2e}")
    assert err < 1e-4

    hist = sess.history[1:]
    bw = sum(c.loop_bytes for c in hist) / sum(c.modelled_s for c in hist)
    print(f"chains: {len(sess.history)}  tiles/chain: {hist[0].num_tiles}  "
          f"slot: {hist[0].slot_bytes / 1e6:.2f} MB")
    up = sum(c.uploaded for c in hist) / 1e6
    dn = sum(c.downloaded for c in hist) / 1e6
    print(f"link traffic: {up:.0f} MB up / {dn:.0f} MB down "
          f"(write-first+cyclic elision on)")
    plan = sess.plan_stats()
    print(f"chain plans: {plan['plan_misses']} analysed once, "
          f"{plan['plan_hits']} replayed from cache "
          f"(hit rate {plan['plan_hit_rate']:.0%})")
    print(f"achieved bandwidth (modelled {hw.name}): {bw / 1e9:.0f} GB/s "
          f"= {bw / 470e9 * 100:.0f}% of the in-core baseline")
    for k, v in summary.items():
        print(f"  summary {k}: {v:.6g} (ref {ref_summary[k]:.6g})")


if __name__ == "__main__":
    main()
