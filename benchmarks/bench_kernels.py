"""Kernel-level microbenchmarks: the two compute hot-spots the paper's
algorithms spend their time in.  On this CPU container we time the jnp
oracle (the Pallas kernels target TPU and run here only under the
interpreter); the derived column reports achieved GB/s / GFLOP/s so the
roofline context is visible.

Every row records the block config it ran (``block``), so the committed
baseline pins not just the time but the tile shape that produced it.
``--autotune`` runs the block-size search (``repro.kernels.autotune``)
and appends ``*_autotune`` rows carrying both ``seconds_default`` and
``seconds_tuned`` — ``compare_baseline`` gates ``tuned <= default``
within a noise band on exactly those rows.  ``--smoke`` shrinks the
search lattice to the CI-sized one; ``--tuned-out`` persists the tuned
table JSON (the bench-smoke artifact).

``--out`` writes the rows as JSON (``{"kernels": [{name, seconds, ...}]}``)
— the committed ``BENCH_kernels_baseline.json`` is this file's output, and
``compare_baseline --kernels-baseline/--kernels-candidate`` gates fresh
runs against it so a kernel regression is caught even when scheduler
noise hides it in end-to-end wall time.
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timeit


def run(out: str | None = None, autotune: bool = False, tuned_out: str | None = None) -> dict:
    from repro.core.apriori import pack_bool_matrix, pack_itemsets
    from repro.kernels import autotune as at
    from repro.kernels import ops
    from repro.kernels.ref import kmeans_assign_ref, support_count_ref

    rng = np.random.default_rng(0)
    cells: list[dict] = []

    # kmeans assignment: N x K distance + argmin
    n, d, k = 65_536, 32, 64
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    f = jax.jit(kmeans_assign_ref)
    jax.block_until_ready(f(x, c))
    dt = timeit(lambda: jax.block_until_ready(f(x, c)))
    flops = 2 * n * d * k
    row("kmeans_assign_jnp", dt, f"gflops={flops / dt / 1e9:.1f};N={n};D={d};K={k}")
    cells.append({"name": "kmeans_assign_jnp", "seconds": dt, "gflops": flops / dt / 1e9})

    # support counting: bitmap AND+match over (tx x candidates)
    ntx, items, cands = 32_768, 128, 512
    dense = rng.random((ntx, items)) < 0.2
    tx = jnp.asarray(pack_bool_matrix(dense))
    sets = [tuple(sorted(rng.choice(items, size=3, replace=False).tolist())) for _ in range(cands)]
    masks = jnp.asarray(pack_itemsets(sets, items))
    g = jax.jit(support_count_ref)
    jax.block_until_ready(g(tx, masks))
    dt = timeit(lambda: jax.block_until_ready(g(tx, masks)))
    gcells = ntx * cands * tx.shape[1]
    row("support_count_jnp", dt, f"gcells={gcells / dt / 1e9:.2f};tx={ntx};cands={cands}")
    cells.append({"name": "support_count_jnp", "seconds": dt, "gcells": gcells / dt / 1e9})

    # Pallas kernels (interpret mode — correctness surface, not speed).
    # Small slices: the interpreter is the correctness path, so these rows
    # gate "did the kernel wrapper get slower", not device throughput.
    km_block = at.DEFAULT_KMEANS_BLOCK
    dt = timeit(
        lambda: jax.block_until_ready(ops.kmeans_assign(x[:4096], c, block_n=km_block)),
        repeats=1,
        warmup=1,
    )
    row("kmeans_assign_pallas_interpret", dt, f"interpret=True;block={km_block}")
    cells.append({"name": "kmeans_assign_pallas_interpret", "seconds": dt, "block": km_block})

    sc_block = list(at.DEFAULT_SUPPORT_BLOCKS)
    dt = timeit(
        lambda: jax.block_until_ready(
            ops.support_count(tx[:4096], masks, block=tuple(sc_block))
        ),
        repeats=1,
        warmup=1,
    )
    row("support_count_pallas_interpret", dt, f"interpret=True;block={tuple(sc_block)}")
    cells.append({"name": "support_count_pallas_interpret", "seconds": dt, "block": sc_block})

    # prune-fused variant: count + threshold in one pass — same tiles, so
    # its cost should track the plain row (the fusion is the win upstream:
    # no separate host threshold sweep per Apriori level)
    dt = timeit(
        lambda: jax.block_until_ready(
            ops.support_count_prune(tx[:4096], masks, 100, block=tuple(sc_block))
        ),
        repeats=1,
        warmup=1,
    )
    row("support_count_prune_interpret", dt, f"interpret=True;block={tuple(sc_block)}")
    cells.append({"name": "support_count_prune_interpret", "seconds": dt, "block": sc_block})

    if autotune:
        # block-size search on the interpret-mode shapes above; _pick
        # keeps the default unless a candidate wins beyond the noise
        # margin, so tuned <= default holds by construction and the
        # compare_baseline gate enforces it stayed that way
        tx_t = jax.lax.bitcast_convert_type(tx[:4096].astype(jnp.uint32), jnp.int32).T
        mk_t = jax.lax.bitcast_convert_type(masks.astype(jnp.uint32), jnp.int32).T
        ent = at.tune_support_count(tx_t, mk_t, interpret=True)
        row(
            "support_count_autotune",
            ent["seconds_tuned"],
            f"default={ent['seconds_default']:.4f}s;block={tuple(ent['config'])}",
        )
        cells.append(
            {
                "name": "support_count_autotune",
                "seconds": ent["seconds_tuned"],
                "seconds_tuned": ent["seconds_tuned"],
                "seconds_default": ent["seconds_default"],
                "block": list(ent["config"]),
            }
        )
        from repro.kernels import pad_to
        from repro.kernels.kmeans_assign import BIG

        xs = x[:4096]
        dp, kp = pad_to(max(d, 128), 128), pad_to(max(k, 128), 128)
        xp = jnp.zeros((xs.shape[0], dp), jnp.float32).at[:, :d].set(xs)
        cp = jnp.full((kp, dp), 0.0, jnp.float32)
        cp = cp.at[:, :d].set(jnp.full((kp, d), BIG, jnp.float32))
        cp = cp.at[:k, :d].set(c)
        ent = at.tune_kmeans_assign(xp, cp, interpret=True)
        row(
            "kmeans_assign_autotune",
            ent["seconds_tuned"],
            f"default={ent['seconds_default']:.4f}s;block={ent['config']}",
        )
        cells.append(
            {
                "name": "kmeans_assign_autotune",
                "seconds": ent["seconds_tuned"],
                "seconds_tuned": ent["seconds_tuned"],
                "seconds_default": ent["seconds_default"],
                "block": ent["config"],
            }
        )
        if tuned_out:
            n_ent = at.save_table(tuned_out)
            print(f"# wrote {tuned_out} ({n_ent} tuned entries)")

    result = {"kernels": cells}
    if out:
        with open(out, "w") as fh:
            json.dump(result, fh, indent=2, sort_keys=True)
        print(f"# wrote {out}")
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None, help="write rows as JSON here")
    ap.add_argument(
        "--autotune",
        action="store_true",
        help="run the block-size search and append tuned-vs-default rows",
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny autotune lattice (CI-sized search, same code path)",
    )
    ap.add_argument(
        "--tuned-out", default=None, help="persist the tuned table JSON here"
    )
    args = ap.parse_args()
    from repro.launch.mesh import enable_compile_cache, tuned_platform

    tuned_platform()  # apply the tuned XLA flag set (GPU) before first use
    enable_compile_cache()
    if args.smoke:
        from repro.kernels import autotune as at

        at.set_smoke(True)
    run(out=args.out, autotune=args.autotune, tuned_out=args.tuned_out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
