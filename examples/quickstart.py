"""Quickstart: the StencilProgram/Session API + out-of-core execution.

A 2-D heat solver whose working set is larger than the configured "fast
memory".  Loops are registered *declaratively*: pass the datasets a kernel
touches and the runtime traces the kernel's accessor calls to infer every
READ stencil and access mode — no hand-built ``Arg(dat, stencil, mode)``
lists.  Backends are selected by name from the registry ("reference",
"resident", "ooc", "ooc-cyclic", "sim", "pallas"); chain plans (dependency
analysis + skewed tile schedule + compiled tiles) are memoised, so repeated
identical chains replay a cached plan.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import Block, Session, TPU_V5E, make_dataset
from repro.kernels import star2d_kernel


def heat(sess: Session, n=512, m=256, steps=8):
    blk = Block("grid", (n, m))
    rng = np.random.RandomState(0)
    u = make_dataset(blk, "u", halo=1, init=rng.rand(n, m).astype(np.float32))
    tmp = make_dataset(blk, "tmp", halo=1)
    interior = ((1, n - 1), (1, m - 1))
    # A declared star sweep (the "pallas" backend fast-paths this one) ...
    diffuse = star2d_kernel("u", "tmp", (0.0, 0.25, 0.25))
    # ... and a plain accessor kernel — stencils/modes inferred by tracing.
    commit = lambda acc: {"u": acc("tmp")}
    for s in range(steps):
        sess.par_loop(f"diffuse{s}", blk, interior, [u, tmp], diffuse)
        sess.par_loop(f"commit{s}", blk, interior, [tmp, u], commit)
    return sess.fetch(u)  # <- chain breaker: analysis + tiling + execution


def main():
    enable_compile_cache()
    ref = heat(Session("reference"))

    # fast memory holds only ~1/4 of the problem: out-of-core streaming
    problem_bytes = 2 * 514 * 258 * 4
    hw = TPU_V5E.with_(fast_capacity=problem_bytes // 4)
    sess = Session("ooc", hw=hw, cyclic=True, prefetch=True)

    # Inspect the Plan IR before anything executes: record one step, ask the
    # planner for the typed instruction stream and its modelled makespan.
    blk = Block("preview", (512, 256))
    rng = np.random.RandomState(0)
    pu = make_dataset(blk, "u", halo=1,
                      init=rng.rand(512, 256).astype(np.float32))
    pt = make_dataset(blk, "tmp", halo=1)
    box = ((1, 511), (1, 255))
    sess.par_loop("p_diffuse", blk, box, [pu, pt],
                  star2d_kernel("u", "tmp", (0.0, 0.25, 0.25)))
    sess.par_loop("p_commit", blk, box, [pt, pu], lambda acc: {"u": acc("tmp")})
    print("--- Session.explain(): the chain's instruction stream ---")
    print("\n".join(sess.explain().splitlines()[:10]))
    print("    ...\n")
    sess.queue.clear()          # preview only — nothing ran

    got = heat(sess)

    assert np.allclose(ref, got, atol=1e-5), "out-of-core result mismatch!"
    st = sess.history[-1]
    plan = sess.plan_stats()
    print(f"problem        : {problem_bytes / 1e6:.1f} MB")
    print(f"fast memory    : {hw.fast_capacity / 1e6:.1f} MB  "
          f"(3 slots x {st.slot_bytes / 1e6:.2f} MB used)")
    print(f"tiles          : {st.num_tiles}")
    print(f"uploaded       : {st.uploaded / 1e6:.1f} MB   "
          f"downloaded: {st.downloaded / 1e6:.1f} MB")
    print(f"modelled step  : {st.modelled_s * 1e3:.2f} ms (model: {hw.name})")
    print(f"chain planning : {plan['plan_misses']} analysed, "
          f"{plan['plan_hits']} cache hits "
          f"({plan['plan_time_s'] * 1e3:.1f} ms total)")
    print("out-of-core result == reference  [OK]")


if __name__ == "__main__":
    main()
